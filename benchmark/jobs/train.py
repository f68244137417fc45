"""The training job: one cell, once, through the entry points a user calls.

``AutoDist(...).function`` builds the plan and the step; ``training.train``
runs the loop with default telemetry, fed host batches from an iterable
whose exhaustion at the deadline ends the run. ``train()`` calls
``on_metrics`` after the meter has read back the loss of steps
1 + k x ``log_every``, which depends on the whole state chain: every call is
a fenced point, and the job takes its timestamps there and nowhere else.

Set-up is everything before the feed hands over its first batch: imports,
weights made on the device from the seed, plan build, compile or cache
load, two warm-up steps, the correctness check against the plain reference.
"""

import math
import os
import shutil
import statistics
import time

import numpy as np

from benchmark import harness, trace_reduce

# Tolerances of the correctness check, with their reason. The system
# computes in bfloat16 (8 bits of mantissa, 2^-8 = 0.4% a rounding) with
# float32 accumulation and parameters; the reference is float32 at "highest"
# matmul precision. Over 24 blocks roundings add up like a random walk, so
# the loss agrees to a few parts in a thousand and the gradient, as a whole
# vector, to a few percent. A dropped term (a residual, a bias, the position
# table, the mask, the 1/sqrt(d) scale) moves either by tens of percent; the
# parameters' and gradients' dtype is checked by name, because bfloat16
# *storage* feeds the same bfloat16 products and would not show in a value.
LOSS_RTOL = 5e-3
GRAD_REL_L2_TOL = 5e-2
HOST_SPANS = ("train.dispatch", "train.readback_wait", "train.data_wait",
              "jit.compile")
TRACE_START_FRACTION = 0.4    # of the window, then the next log boundary
TRACE_SECONDS = 3.0           # at least this long, then the next log boundary


def undisturbed_step_seconds(step_seconds) -> float:
    """The seconds a step takes when nothing disturbs the run: the lower
    quartile of the log periods' seconds a step (interpolated inside the
    data). A period cannot be shorter than the device's work plus the
    loop's own gap at the boundary, and the timestamp that ends a period is
    taken before the next period is dispatched, so what the machine adds
    is one-sided: a stall only lengthens periods. On the chip one period in
    sixty of ``gpt2m-pretrain-1k`` took 0.39 s longer inside the loop's
    wait for the device (my chip runs, PR 22), and in the driver's first
    check of PR 22 two runs in six of that cell read some 5% low by the
    median of six periods, which is what such a stall in every second
    period gives, while the other ten agreed to 0.01%. The lower quartile
    holds while more than a quarter of the periods are clean; a change to
    the program that slows every period moves it like any other statistic.
    What it cannot see, slow periods among clean ones,
    ``window_mean_vs_quoted_pct``, ``window_slow_periods_pct`` and
    ``window_rate_iqr_pct`` report."""
    if len(step_seconds) == 1:
        return step_seconds[0]
    return statistics.quantiles(step_seconds, n=4, method="inclusive")[0]


class Feed:
    """Cycles the seeded pool of host batches until the deadline. The first
    ``next`` is the start of the measured window."""

    def __init__(self, pool, seconds: float, on_start):
        self.pool, self.seconds, self.on_start = pool, seconds, on_start
        self.t_start = None
        self.yielded = 0

    def __iter__(self):
        return self

    def __next__(self):
        now = time.perf_counter()
        if self.t_start is None:
            self.t_start = now
            self.on_start()
        elif now - self.t_start >= self.seconds:
            raise StopIteration
        batch = self.pool[self.yielded % len(self.pool)]
        self.yielded += 1
        return batch


def _resource_spec(chips: int, mesh: dict):
    from autodist_tpu import ResourceSpec
    if chips == 1:
        return None
    return ResourceSpec(resource_info={
        "nodes": [{"address": "localhost", "tpus": chips, "chief": True}],
        "mesh": mesh})


def check_against_reference(cell, built) -> dict:
    """Loss and gradients of the system's own loss function, on the cell's
    own parameters and a seeded sample, against the plain float32
    reference. Returns the facts; the caller decides ``correct``."""
    import jax
    import jax.numpy as jnp

    reference = cell.load_module("reference", cell.config["family"])
    sample = {k: jnp.asarray(v) for k, v in built.sample.items()}
    sys_loss, sys_grads = jax.jit(jax.value_and_grad(built.loss_fn))(
        built.params, sample)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p, b: reference.loss(p, b, **built.reference_config)))(
                built.params, sample)

    @jax.jit
    def compare(a, b):
        diff = sum(jnp.sum(jnp.square(x.astype(jnp.float32) - y))
                   for x, y in zip(jax.tree_util.tree_leaves(a),
                                   jax.tree_util.tree_leaves(b)))
        norm = sum(jnp.sum(jnp.square(y)) for y in jax.tree_util.tree_leaves(b))
        return jnp.sqrt(diff / norm)

    dtypes = {str(x.dtype) for tree in (built.params, sys_grads)
              for x in jax.tree_util.tree_leaves(tree)}
    sys_loss, ref_loss = float(sys_loss), float(ref_loss)
    grad_rel_l2 = float(compare(sys_grads, ref_grads))
    loss_rel = abs(sys_loss - ref_loss) / abs(ref_loss)
    return {"system_loss": sys_loss, "reference_loss": ref_loss,
            "loss_rel_diff": loss_rel, "loss_rtol": LOSS_RTOL,
            "grad_rel_l2": grad_rel_l2, "grad_rel_l2_tol": GRAD_REL_L2_TOL,
            "param_and_grad_dtypes": sorted(dtypes),
            "agrees": bool(loss_rel <= LOSS_RTOL
                           and grad_rel_l2 <= GRAD_REL_L2_TOL
                           and dtypes == {"float32"})}


def compiled_facts(runner, state, batch) -> dict:
    """The compiled step's own account, and where the parameters live."""
    import jax
    facts = harness.compiled_facts(
        runner.compiled_step(state, runner.shard_batch(batch)))
    facts["param_device_set_sizes"] = sorted({
        len(leaf.sharding.device_set)
        for leaf in jax.tree_util.tree_leaves(state.params)})
    return facts


class Tracer:
    """Drives the profiler from the loop's fenced log boundaries: on from the
    first boundary after ``TRACE_START_FRACTION`` of the window, off at the
    first boundary at least ``TRACE_SECONDS`` later, so the traced window
    holds whole log periods. ``state`` is off (an untraced run), armed, on
    or done."""

    def __init__(self, enabled: bool, trace_dir: str, seconds: float):
        self.state = "armed" if enabled else "off"
        self.dir, self.seconds = trace_dir, seconds
        self.t_on = self.mark_ns = self.step_on = self.step_off = None

    def at_boundary(self, step_no: int, since_start: float):
        import jax
        now = time.perf_counter()
        if self.state == "armed" and \
                since_start >= TRACE_START_FRACTION * self.seconds:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.state, self.step_on = "on", step_no
            self.t_on, self.mark_ns = time.perf_counter(), time.perf_counter_ns()
            with jax.profiler.TraceAnnotation("bench.window_begin"):
                pass
        elif self.state == "on" and \
                now - self.t_on >= min(TRACE_SECONDS, 0.3 * self.seconds):
            with jax.profiler.TraceAnnotation("bench.window_end"):
                pass
            jax.profiler.stop_trace()
            self.state, self.step_off = "done", step_no

    def abandon(self):
        import jax
        if self.state == "on":
            jax.profiler.stop_trace()


def set_up(cell, seed: int, events) -> dict:
    """Everything before the window, with its cost by phase on standard
    error: weights, plan, first and second step, the compiled step's own
    account, the check against the reference."""
    import jax

    from autodist_tpu import AutoDist, strategy as strategies

    traffic, chips = cell.traffic, cell.chips
    dp = int(np.prod(list(traffic["mesh"].values())))
    if dp != chips:
        raise harness.BenchmarkError(f"mesh {traffic['mesh']} is not {chips} chip(s)")
    accum = traffic["accumulation"]
    family = cell.load_module("families", cell.config["family"])
    phase = harness.Phases(events)
    built = family.build(cell.config, traffic, seed,
                         traffic["micro_batch"] * accum * dp)
    jax.block_until_ready(built.params)
    phase.done(f"{cell.name}: weights and batch pool from seed {seed}")
    ad = AutoDist(_resource_spec(chips, traffic["mesh"]),
                  strategy_builder=getattr(strategies, traffic["strategy"])())
    step = ad.function(built.loss_fn, built.params, built.optimizer,
                       example_batch=built.pool[0], accumulation_steps=accum)
    plan_build_s = phase.done("plan built, state placed")
    warm_losses = [harness.fence(step(built.pool[0]))]
    first_step_s = phase.done("first step (compile or cache load)")
    warm_losses.append(harness.fence(step(built.pool[1 % len(built.pool)])))
    phase.done("second step")
    runner = step.runner
    compiled = compiled_facts(runner, step.get_state(), built.pool[0])
    del step        # and with it the warm-up's state: train() makes its own
    phase.done("compiled step read (memory, kernels, collectives)")
    reference = check_against_reference(cell, built)
    phase.done(f"checked against the reference: {reference}")
    return {"built": built, "runner": runner, "compiled": compiled,
            "reference": reference, "warm_losses": warm_losses,
            "plan_build_s": plan_build_s, "first_step_s": first_step_s}


def run(cell, *, seed: int, seconds: float, trace: bool, require_tpu: bool,
        clock0, peaks, keep_trace: str = "") -> dict:
    """``clock0`` is (process age in seconds, ``perf_counter()``) taken as
    one reading when the process began its own code."""
    from autodist_tpu import telemetry
    from autodist_tpu.training import train

    chips, log_every = cell.chips, cell.traffic["log_every"]
    with harness.CompileEvents() as build_events:
        ready = set_up(cell, seed, build_events)
    built, compiled, reference = (ready["built"], ready["compiled"],
                                  ready["reference"])

    trace_dir = os.path.join(harness.work_dir(cell.root), "trace", cell.name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    tracer = Tracer(trace, trace_dir, seconds)
    window_events = harness.CompileEvents()
    feed = Feed(built.pool, seconds, on_start=window_events.__enter__)
    boundaries = []     # (step, perf_counter, loss, tracer state) at fenced boundaries

    def on_metrics(step_no, loss, rate):
        now = time.perf_counter()
        boundaries.append((step_no, now, float(loss), tracer.state))
        tracer.at_boundary(step_no, now - feed.t_start)

    if trace:
        telemetry.enable()
    try:
        final = train(ready["runner"], built.params, feed, steps=10**9,
                      log_every=log_every, on_metrics=on_metrics)
        final_step = int(final.step)
    finally:
        if feed.t_start is not None:
            window_events.__exit__(None, None, None)
        tracer.abandon()
        spans = telemetry.snapshot_spans() if trace else []
        if trace:
            telemetry.disable()
    setup_s = clock0[0] + (feed.t_start - clock0[1])

    # ------------------------------------------------------------ the record
    # A traced run times only the periods before the profiler came on.
    timed = [b for b in boundaries if b[3] in ("off", "armed")]
    if len(timed) < 2:
        raise harness.BenchmarkError(
            f"{len(timed)} fenced log boundaries in {seconds}s: the window is "
            f"too short for log_every {log_every}")
    (s0, t0, _, _), (s1, t1, _, _) = timed[0], timed[-1]
    step_seconds = [(b[1] - a[1]) / (b[0] - a[0]) for a, b in zip(timed, timed[1:])]
    per_chip = built.tokens_per_step / chips
    rate = per_chip / undisturbed_step_seconds(step_seconds)
    losses = [b[2] for b in boundaries]
    bad = [i for i, x in enumerate(losses) if not math.isfinite(x)]

    checks = {
        "reference": reference,
        "warmup_losses": ready["warm_losses"],
        "first_boundary_loss": losses[0], "last_boundary_loss": losses[-1],
        "losses_finite": not bad,
        "loss_fell": bool(losses[-1] < losses[0]),
        "compile_requests_in_window": window_events.requests,
        "cache_misses_in_window": window_events.misses,
        "compiled": compiled,
        "steps_completed": final_step, "log_boundaries": len(boundaries),
        "step_seconds_by_period": step_seconds,
    }
    correct = (reference["agrees"] and checks["losses_finite"]
               and checks["loss_fell"] and window_events.requests == 0
               and final_step == feed.yielded)
    if require_tpu:   # interpret mode on the CPU lowers a kernel to plain ops
        correct = correct and (compiled["tpu_custom_call"]
                               == bool(cell.config.get("expects_pallas")))
    if chips > 1:
        correct = correct and compiled["param_device_set_sizes"] == [chips] \
            and bool({"all-reduce", "reduce-scatter"} & set(compiled["collectives"]))

    device = harness.describe_device()
    device["memory_peak_bytes"] = harness.memory_peak_bytes()
    record = {
        "cell": cell, "peaks": peaks, "device": device, "correct": bool(correct),
        "attempted": feed.yielded,
        "failed": (len(losses) - bad[0]) * log_every if bad else 0,
        "checks": checks,
        "end_to_end": {"tokens_per_s_per_chip": rate, "setup_s": setup_s},
        "mean_rate": per_chip * (s1 - s0) / (t1 - t0),
        "period_rates": [per_chip / s for s in step_seconds],
        "train_flops_per_token": built.train_flops_per_token,
        "kernel_cost_per_step": built.kernel_cost_per_step,
        "plan_build_s": ready["plan_build_s"],
        "first_step_s": ready["first_step_s"],
        "cache_hits": build_events.hits + window_events.hits,
        "cache_misses": build_events.misses + window_events.misses,
        "compiled": compiled, "boundaries": boundaries,
        "dispatch_spans_ms": [
            dur_ns * 1e-6 for name, _tid, t0_ns, dur_ns, _args in spans
            if name == "train.dispatch" and t0_ns * 1e-9 >= feed.t_start],
    }
    if trace:
        record.update(_traced(trace_dir, tracer, spans, record, require_tpu,
                              keep_trace))
        shutil.rmtree(trace_dir, ignore_errors=True)   # tens of MB a run
    return record


def _traced(trace_dir: str, tracer: Tracer, spans, record: dict,
            require_tpu: bool, keep_trace: str) -> dict:
    """Reduce the device trace and put the program's host spans on its
    clock (both clocks are read at the ``bench.window_begin`` mark)."""
    import glob
    if tracer.state != "done":
        raise harness.BenchmarkError(
            f"the trace did not complete inside the window (state "
            f"{tracer.state}): window too short for the cell's log period")
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise harness.BenchmarkError(f"no .xplane.pb under {trace_dir}")
    raw = trace_reduce.read_xplane(files[-1])
    if keep_trace:
        trace_reduce.save_json(raw, keep_trace)
    if not require_tpu and not raw.devices:
        # CPU rehearsal: the trace has the marks and no device plane, so
        # there is no device number to give, under any name.
        return {"trace": None, "trace_marks": raw.marks}
    summary = trace_reduce.summarize(raw)
    offset = raw.marks["bench.window_begin"] - tracer.mark_ns * 1e-9
    host = [(name, t0_ns * 1e-9 + offset, (t0_ns + dur_ns) * 1e-9 + offset)
            for name, _tid, t0_ns, dur_ns, _args in spans
            if name in HOST_SPANS]
    in_window = [s for s in host if s[2] > summary.window[0]
                 and s[1] < summary.window[1]]
    steps = tracer.step_off - tracer.step_on
    first = min(summary.devices)
    worst, worst_idle = summary.worst_idle_device()
    idle_gaps = trace_reduce.attribute_gaps(
        summary.devices[first].idle, in_window, 10)
    if len(summary.devices) > 1:
        idle_gaps = idle_gaps[:9] + [[
            f"(chip {worst}, the idlest: all its gaps)",
            worst_idle * summary.window_s]]
    breakdown = {"device_ops": summary.top_ops(10), "idle_gaps": idle_gaps}
    device = dict(record["device"], busy_s=summary.busy_s,
                  window_s=summary.window_s)
    return {"trace": summary, "trace_steps": steps, "host_spans": in_window,
            "breakdown": breakdown, "device": device}
