"""Training loop with automatic checkpoint/resume.

The reference left the loop to user scripts (session.run loops, Keras fit) and
proved resumability with its NFS saver case — chief-gated saves on a shared
filesystem (``tests/integration/cases/c10.py:1-12``). This is that contract as
an API: periodic chief-gated saves under original names, automatic resume from
the latest checkpoint, throughput metering, and a final save — so a preempted
run restarted with the same command continues where it stopped.
"""

from typing import Any, Callable, Iterable, Optional, Union

import jax
import numpy as np

from autodist_tpu import const, telemetry
from autodist_tpu.checkpoint.saver import Saver
from autodist_tpu.data import prefetch as _prefetch
from autodist_tpu.parallel import recovery as _recovery
from autodist_tpu.runner import MicroBatched, TrainState
from autodist_tpu.testing import faults as _faults
from autodist_tpu.telemetry import health as _health
from autodist_tpu.telemetry import history as _history
from autodist_tpu.telemetry import memplane as _memplane
from autodist_tpu.telemetry import openmetrics as _openmetrics
from autodist_tpu.telemetry import profiling as _profiling
from autodist_tpu.utils import logging
from autodist_tpu.utils.metrics import ThroughputMeter

PyTree = Any


def _observe_health(monitor, runner, step: int, losses,
                    state: TrainState):
    """Feed the health monitor at a log boundary (where the loss readback
    already synced) and apply the policy: ``losses`` is the period's
    per-step loss values (host-side), the bundle is the runner's latest
    device readback. Raises :class:`telemetry.HealthHalt` with the LIVE
    state attached under ``AUTODIST_HEALTH_ACTION=halt``, or
    :class:`telemetry.HealthRecover` under ``recover`` — ``train()``'s
    retry wrapper catches the latter, rolls back to the newest
    last-known-good snapshot, and resumes."""
    bundle = getattr(runner, "last_health", None)
    if bundle is not None:
        bundle = jax.device_get(bundle)
    anomalies = monitor.observe(step, losses, bundle)
    if anomalies and monitor.should_recover:
        raise _health.HealthRecover(step, state, anomalies)
    if anomalies and monitor.should_halt:
        raise _health.HealthHalt(step, state, anomalies)


def _make_meter(first_batch: PyTree, batch_size: Optional[int],
                log_every: int) -> ThroughputMeter:
    """Meter sized lazily from the first batch: the largest leading dim fixes
    the example count per step (shared by the per-step and unrolled loops so
    their examples/s can never diverge for identical configs). A batch that
    already went through ``shard_batch`` under gradient accumulation carries
    ``MicroBatched`` leaves laid out ``[k, B/k, ...]`` — fold those back to
    ``B`` (the prefetched per-step loop meters the transformed batch)."""
    n = batch_size
    if n is None:
        dims = []
        for leaf in jax.tree_util.tree_leaves(
                first_batch, is_leaf=lambda x: isinstance(x, MicroBatched)):
            if isinstance(leaf, MicroBatched):
                v = leaf.value
                if getattr(v, "ndim", 0) >= 2:
                    dims.append(v.shape[0] * v.shape[1])
            elif getattr(leaf, "ndim", 0) >= 1:
                dims.append(leaf.shape[0])
        n = max(dims, default=1)
    return ThroughputMeter(batch_size=n, log_every=log_every, log=False)


def train(runner, params: PyTree,
          batches: Union[Callable[[int], PyTree], Iterable[PyTree]],
          steps: int,
          checkpoint_dir: Optional[str] = None,
          checkpoint_name: str = "model",
          save_every: int = 1000,
          max_to_keep: int = 5,
          log_every: int = 100,
          batch_size: Optional[int] = None,
          is_chief: Optional[bool] = None,
          resume: bool = True,
          async_save: bool = False,
          on_metrics: Optional[Callable[[int, float, float], None]] = None,
          eval_every: int = 0,
          eval_batch: Any = None,
          eval_fn: Optional[Callable] = None,
          on_eval: Optional[Callable[[int, Any], None]] = None,
          unroll: Optional[int] = None,
          prefetch_depth: Optional[int] = None,
          health_monitor: Optional["_health.HealthMonitor"] = None) -> TrainState:
    """Run ``steps`` global steps, checkpointing and resuming automatically.

    ``batches``: either ``fn(step_index) -> batch`` or an iterable of batches
    (exhaustion ends the run early). In a multi-process SPMD program
    (``jax.process_count() > 1``) saves are COLLECTIVE: every process calls
    :meth:`Saver.save` at the same step, writes the state shards it owns, and
    only the chief publishes the manifest + rotation — the c10
    shared-filesystem protocol against cross-process-sharded state. With one
    process (or async-PS worker roles), saves stay chief-only.
    ``async_save=True`` makes PERIODIC saves double-buffered (device snapshot
    synchronous, file IO behind the step loop — :meth:`Saver.save`); the
    final save is always synchronous, so the returned state is durably on
    disk. ``on_metrics(step, loss, rate)`` fires every
    ``log_every`` steps. With ``eval_every`` and ``eval_batch``, the runner's
    forward-only :meth:`evaluate` runs every ``eval_every`` steps on the
    current params (``eval_fn`` defaults to the loss) and ``on_eval(step,
    value)`` receives the result. Returns the final :class:`TrainState`.

    ``unroll=None`` (the default) adopts the runner's tuned plan when one is
    attached (``create_distributed_session(tune=True)`` sets
    ``runner.tuned_plan``; its ``unroll`` is the autotuner's measured
    winner) and otherwise behaves as ``unroll=1``; pass an explicit value
    to override the tuned knob.

    ``unroll=K`` (K > 1) switches the loop to the fused dispatch-ahead
    pipeline: K consecutive batches are stacked into one pre-sharded block and
    run as ONE compiled K-step program (:meth:`DistributedRunner.run_many` —
    bit-identical to K per-step calls), while the host gathers and pre-shards
    the next block behind the running one. Checkpoint and eval cadence points
    force block boundaries, so saves/evals fire at exactly the per-step
    loop's steps and resume semantics are unchanged (step i still consumes
    batch i); only logging moves to block granularity (the first block is the
    meter's warmup, periods close at the first block end with ``log_every``
    post-warmup steps, and ``on_metrics`` receives the block's last loss).
    Runners without fused support (async-PS, remote workers) fall back to the
    per-step loop with a warning.

    ``prefetch_depth`` arms the async input pipeline
    (:mod:`autodist_tpu.data.prefetch`): a background producer pulls up to
    ``prefetch_depth`` batches (blocks, under ``unroll=K``) ahead of the
    step and applies the feed remapping (``shard_batch``/``shard_block``)
    there, so host loading and host->HBM transfer overlap the running
    step; ``train.data_wait`` then measures only the residual queue wait,
    while the ``data.producer_wait`` counter keeps naming a slow loader.
    ``None`` adopts the tuned plan's ``prefetch_depth`` when one is
    attached and nonzero, else the ``AUTODIST_PREFETCH_DEPTH`` flag
    (default 0 = the synchronous feed, batches pulled exactly at their
    step). Prefetching calls the batch source up to ``prefetch_depth``
    items ahead (an iterable may be advanced past the last consumed step
    at shutdown); exceptions from the source re-raise at the consuming
    step, and exhaustion ends the run exactly like the synchronous path.

    ``health_monitor`` overrides the ``AUTODIST_HEALTH`` default (a
    :class:`telemetry.HealthMonitor`, or the flag builds one): the monitor
    consumes each log period's per-step losses plus the runner's fused
    on-device numerics bundle at the SAME boundary where the loss readback
    already syncs — zero extra dispatches, zero extra syncs. Anomalies
    (NaN/Inf, loss spikes) become ``health.anomaly`` events and follow the
    ``AUTODIST_HEALTH_ACTION`` policy; ``halt`` raises
    :class:`telemetry.HealthHalt` carrying the live state. Monitoring needs
    ``log_every > 0`` (boundaries are where readbacks happen).

    ``AUTODIST_HEALTH_ACTION=recover`` (and its alert-engine twin
    ``AUTODIST_ALERT_ACTION=recover``) turns detection into self-healing:
    the loop keeps a bounded ring of last-known-good states captured at
    health-clean log boundaries, and an anomaly ROLLS BACK to the newest
    good one and resumes — replaying the rolled-back steps exactly when
    ``batches`` is a callable (an iterable source continues on its next
    unconsumed items instead). At most ``AUTODIST_RECOVER_MAX`` rollback
    attempts; exhaustion (or an anomaly before any healthy boundary)
    escalates to the existing :class:`telemetry.HealthHalt` /
    :class:`telemetry.AlertHalt`. See ``docs/usage/resilience.md``.
    """
    if unroll is None:
        tuned = getattr(runner, "tuned_plan", None)
        unroll = int(getattr(tuned, "unroll", 1) or 1)
        if unroll > 1:
            logging.info("train: adopting tuned plan unroll=%d (%s; pass "
                         "unroll= explicitly to override)", unroll,
                         getattr(tuned, "name", "tuned plan"))
    if unroll < 1:
        raise ValueError("unroll must be >= 1")
    if prefetch_depth is None:
        tuned = getattr(runner, "tuned_plan", None)
        tuned_depth = int(getattr(tuned, "prefetch_depth", 0) or 0)
        if tuned_depth > 0:
            logging.info("train: adopting tuned plan prefetch_depth=%d "
                         "(pass prefetch_depth= explicitly to override)",
                         tuned_depth)
            prefetch_depth = tuned_depth
        else:
            prefetch_depth = _prefetch.default_prefetch_depth()
    prefetch_depth = max(0, int(prefetch_depth))
    if eval_every and eval_batch is None:
        raise ValueError("eval_every needs an eval_batch")
    if is_chief is None:
        is_chief = const.is_chief_process()
    # Scrape endpoint: AUTODIST_METRICS_PORT attaches /metrics + /healthz to
    # the trainer process too (PSServer/InferenceServer processes attach in
    # their constructors; the process-global exporter binds once either way).
    _openmetrics.maybe_serve()
    # Sharded (multi-process SPMD) saves are collective: every process must
    # participate — each writes the shards it owns; the Saver itself gates
    # manifest/rotation to process 0. Chief-only gating remains for
    # single-process programs (incl. async-PS roles, where each process is
    # its own jax program).
    save_participant = is_chief or jax.process_count() > 1
    saver = Saver(max_to_keep=max_to_keep) if checkpoint_dir else None
    prefix_base = f"{checkpoint_dir}/{checkpoint_name}" if checkpoint_dir else None

    state = None
    if saver is not None and resume:
        latest = Saver.latest_checkpoint(checkpoint_dir, name=checkpoint_name)
        if latest is not None:
            state = saver.restore(latest, runner=runner)
            logging.info("train: resumed from %s at step %d", latest,
                         int(state.step))
    if state is None:
        state = runner.init(params)

    next_batch = batches if callable(batches) else None
    batch_iter = iter(batches) if next_batch is None else None

    start = int(state.step)
    if batch_iter is not None and start > 0:
        # Resume with an iterable: fast-forward so step i still consumes batch i —
        # replaying from item 0 would retrain on already-seen data and break the
        # identical-resume contract.
        logging.info("train: fast-forwarding batch iterator by %d consumed steps",
                     start)
        for _ in range(start):
            try:
                next(batch_iter)
            except StopIteration:
                return state
    monitor = health_monitor if health_monitor is not None \
        else _health.HealthMonitor.from_env()
    if monitor is not None and not log_every:
        logging.warning("train: health monitors need log_every > 0 (the "
                        "bundle readback rides log boundaries); disabling "
                        "them for this run")
        monitor = None
    use_blocks = (unroll > 1 and getattr(runner, "supports_run_many", False)
                  and not getattr(runner, "_is_remote_worker", False))
    if unroll > 1 and not use_blocks:
        logging.warning(
            "train: unroll=%d requested but %s has no fused multi-step path "
            "(async/remote regime); falling back to per-step dispatch",
            unroll, type(runner).__name__)

    def _finish(final_state: TrainState) -> TrainState:
        # End-of-run attribution flush (the health monitors' PR 8 contract,
        # re-established here): a final partial period — steps not a
        # multiple of log_every, or a run shorter than one period — still
        # reaches the series; require_steps drops a dispatch-less tail.
        # BEFORE the final save: a multi-second synchronous checkpoint
        # would otherwise land in the tail period's compute residual and
        # inflate the profile's period-weighted step_s.
        if _profiling.active():
            _profiling.observe_period(int(final_state.step),
                                      require_steps=True)
        # End-of-run history flush (forced past the throttle): a run shorter
        # than one min_interval_s window still leaves at least one sample —
        # and its final alert tick — in the ring/shards. AFTER the closing
        # observe_period so the sample carries the tail period's gauges;
        # BEFORE the final save so a halt-action alert stops us with the
        # state unsaved-but-LIVE on the exception, exactly like HealthHalt.
        try:
            _history.maybe_sample(int(final_state.step), reason="final",
                                  force=True)
        except telemetry.AlertHalt as e:
            e.state = final_state
            raise
        # Final save stays synchronous: train() returning means the state is
        # durably on disk (save() joins any in-flight periodic write first).
        if saver is not None and save_participant and int(final_state.step) > start:
            with telemetry.span("train.checkpoint", final=True):
                saver.save(final_state, prefix_base, runner=runner)
        if saver is not None:
            saver.wait()
        # Per-run profile store: with the attribution plane armed and
        # AUTODIST_PROFILE_DIR set, the run's profile JSON (program costs +
        # attribution series) lands on disk for adprof/costmodel.
        _profiling.maybe_write_profile()
        return final_state

    # Recover-and-resume policy (parallel/recovery.py): under
    # AUTODIST_HEALTH_ACTION=recover (or the alert-engine twin) the loops
    # push the state into a bounded last-known-good ring at every HEALTHY
    # log boundary, and an anomaly rolls back to the newest good snapshot
    # and re-enters the loop — bounded by AUTODIST_RECOVER_MAX attempts
    # before escalating to the existing halt.
    recover_armed = (
        (monitor is not None and monitor.config.action == "recover")
        or str(const.ENV.AUTODIST_ALERT_ACTION.val) == "recover")
    ring = None
    if recover_armed:
        # Ring entries must OWN their buffers: the sync runner's step
        # DONATES its input state, so a bare reference would be deleted by
        # the dispatch right after the push. One fused on-device copy per
        # healthy boundary (sharding-preserving; recover is opt-in and log
        # boundaries are sparse — the copy is the price of a rollback
        # target that survives donation).
        import jax.numpy as jnp
        ring = _recovery.SnapshotRing(copy_fn=jax.jit(
            lambda s: jax.tree_util.tree_map(jnp.copy, s)))
    if ring is not None and batch_iter is not None:
        logging.warning(
            "train: recover action with an ITERABLE batch source — a "
            "rollback cannot replay consumed batches, so the resumed loop "
            "continues on the next unconsumed ones (pass a callable "
            "batches(step) source for exact replay)")

    def _run_attempt(attempt_state: TrainState) -> TrainState:
        """One pass of the chosen loop from ``attempt_state``'s own step —
        feeds are (re)built per attempt so a rollback's replay pulls the
        rolled-back step range, not the crashed attempt's readahead."""
        start_i = int(attempt_state.step)
        if use_blocks:
            # Async input pipeline for the fused loop: the producer gathers
            # the NEXT blocks (clipped at the same cadence boundaries the
            # sync path uses) and pre-shards them (shard_block = stacking +
            # async device_put) up to prefetch_depth blocks ahead, so the
            # BatchBlock queue feeds without blocking at block assembly.
            feed = None
            if prefetch_depth > 0:
                feed = _BlockFeed(
                    runner, next_batch, batch_iter, start_i, steps, unroll,
                    _boundary_fn(steps,
                                 save_every if saver is not None else 0,
                                 eval_every), prefetch_depth)
            try:
                return _unrolled_loop(
                    runner, attempt_state, next_batch, batch_iter, start_i,
                    steps, unroll, saver, prefix_base, save_participant,
                    save_every, async_save, log_every, batch_size,
                    on_metrics, eval_every, eval_batch, eval_fn, on_eval,
                    monitor, feed, ring)
            finally:
                if feed is not None:
                    feed.close()
        # Async input pipeline: with prefetch_depth > 0 a background
        # producer pulls host batches AND applies the feed remapping
        # (shard_batch = async device_put) up to `depth` ahead, so the
        # loop's train.data_wait span measures only the residual queue
        # wait. The producer books data.producer_wait/queue_depth, keeping
        # a slow loader visible.
        feed = _step_feed(runner, next_batch, batch_iter, start_i, steps,
                          prefetch_depth) if prefetch_depth > 0 else None
        try:
            return _per_step_loop(
                runner, attempt_state, feed, next_batch, batch_iter,
                start_i, steps, saver, prefix_base, save_participant,
                save_every, async_save, log_every, batch_size, on_metrics,
                eval_every, eval_batch, eval_fn, on_eval, monitor, ring)
        finally:
            if feed is not None:
                feed.close()

    attempt = 0
    last_fail_step = None
    while True:
        try:
            state = _run_attempt(state)
            break
        except (_health.HealthRecover, telemetry.AlertRecover) as e:
            # AUTODIST_RECOVER_MAX bounds attempts PER INCIDENT, not per
            # run: an anomaly at a LATER step than the last one means the
            # earlier incident was overcome (training progressed past it),
            # so the budget resets — three transient spikes hours apart
            # must not spend a lifetime cap and turn the fourth into a
            # halt. A repeat at the same (or an unknown) step is the same
            # incident and keeps counting toward escalation.
            fail_step = getattr(e, "step", None)
            if fail_step is not None and last_fail_step is not None \
                    and fail_step > last_fail_step:
                attempt = 0
            if fail_step is not None:
                last_fail_step = fail_step
            attempt += 1
            # Returns the newest good state (re-seeding an async runner's
            # service), or escalates to HealthHalt/AlertHalt when the ring
            # is empty or AUTODIST_RECOVER_MAX is spent.
            state = _recovery.rollback(e, ring, attempt,
                                       _recovery.recover_max(),
                                       runner=runner)
    return _finish(state)


def _step_feed(runner, next_batch, batch_iter, start: int, steps: int,
               depth: int, workers: Optional[int] = None):
    """The per-step loop's async feed: a :class:`PrefetchProducer` pulling
    the batch source in step order and applying ``runner.shard_batch``
    (when the runner has one — async/remote regimes prefetch host batches
    only) on the producer side. Pulls stop at ``steps``: a callable
    source is never invoked past the last step it could train (readahead
    must not call user code out of the run's contract)."""
    if next_batch is not None:
        counter = iter(range(start, steps))
        pull = lambda: next_batch(next(counter))  # noqa: E731
    else:
        pull = lambda: next(batch_iter)           # noqa: E731
    shard = getattr(runner, "shard_batch", None)
    transform = shard if (callable(shard)
                          and not getattr(runner, "_is_remote_worker",
                                          False)) else None
    return _prefetch.PrefetchProducer(pull, transform, depth=depth,
                                      workers=workers
                                      or _prefetch.default_prefetch_workers(),
                                      name="train-feed")


def _per_step_loop(runner, state: TrainState, feed, next_batch, batch_iter,
                   start: int, steps: int, saver, prefix_base,
                   save_participant, save_every: int, async_save: bool,
                   log_every: int, batch_size: Optional[int], on_metrics,
                   eval_every: int, eval_batch, eval_fn, on_eval,
                   monitor, ring=None) -> TrainState:
    """The classic one-dispatch-per-step loop (``unroll=1``), fed either
    synchronously or from the async prefetch producer (``feed``).
    ``ring`` (a :class:`recovery.SnapshotRing`) receives the state at every
    boundary that closes healthy — the recover action's rollback targets."""
    meter = None
    loss = None
    # Health monitoring: per-step device losses accumulate here (tiny device
    # scalars, no sync) and are read back together at the log boundary — so
    # the spike detector sees EVERY step's loss while the loop still syncs
    # only once per period.
    pending_losses = []
    for step_i in range(start, steps):
        if feed is not None:
            try:
                with telemetry.span("train.data_wait"):
                    batch = next(feed)
            except StopIteration:
                logging.info("train: batch iterator exhausted at step %d",
                             step_i)
                break
        elif next_batch is not None:
            with telemetry.span("train.data_wait"):
                batch = next_batch(step_i)
        else:
            try:
                with telemetry.span("train.data_wait"):
                    batch = next(batch_iter)
            except StopIteration:
                logging.info("train: batch iterator exhausted at step %d", step_i)
                break
        if _faults.armed() and _faults.should_fire("nan_grads", step=step_i):
            # Chaos harness (testing/faults.py): NaN-fill the batch's float
            # leaves so the REAL compiled step produces real NaN gradients —
            # the recover-action tests and bench drive genuine anomalies,
            # not mocks. Un-armed cost: one module-global read per step.
            logging.warning("faults: injecting NaN batch at step %d", step_i)
            batch = _faults.corrupt_batch(batch)
        with telemetry.span("train.dispatch"):
            state, fetched = runner.run(state, batch)
        loss = fetched[0] if isinstance(fetched, tuple) else fetched
        if monitor is not None:
            pending_losses.append(loss)
        if meter is None and log_every:
            meter = _make_meter(batch, batch_size, log_every)
        if meter is not None:
            # The meter syncs (device->host read of the loss) only at its period
            # boundaries — one boundary per log_every steps, not per step — and
            # excludes its warmup step, so boundaries land at 1 + k*log_every
            # local steps.
            rate = meter.step(sync=loss)
            if rate is not None:
                # From the meter's return to the end of the boundary block:
                # what the device waits for before the next feed. Its two
                # children are what only a traced run pays (planes) and the
                # caller's callback; the rest is what every run pays.
                with telemetry.span("train.boundary"):
                    # The period's attribution closes HERE — after the meter's
                    # boundary sync recorded its readback span, before the
                    # snapshot below is emitted — so the train.attr.*/mfu
                    # gauges it books describe exactly this period.
                    attr = _profiling.observe_period(step_i + 1) \
                        if _profiling.active() else None
                    # Async-PS runs append their transport accounting (zero-copy
                    # wire counters) so per-period logs show parameter/gradient
                    # traffic next to throughput. `q` is the input queue depth
                    # (the prefetch producer's fill with prefetch_depth > 0,
                    # else 0 — 0 under prefetch means the loader is not keeping
                    # up), `rb` the seconds this period spent blocked on
                    # device->host readback — together they say whether a slow
                    # period was compute, readback, or host-side stall, from
                    # the log line alone.
                    stats = getattr(runner, "wire_stats", None)
                    stats = stats() if callable(stats) else None
                    logging.info("train: step %d loss %.4f %.1f examples/s "
                                 "| q %d rb %.3fs%s%s",
                                 step_i + 1, float(loss), rate,
                                 feed.queue_depth() if feed is not None else 0,
                                 meter.last_readback_s,
                                 f" | {stats.format_line()}" if stats else "",
                                 _profiling.format_attr_line(attr))
                    # The period's throughput as a gauge: the fleet console
                    # (tools/adfleet.py) compares steps/s across processes off
                    # the status opcode, so the rate must live in the registry,
                    # not just the log line. One gauge set per log boundary.
                    telemetry.gauge("train.steps_per_s").set(
                        round(rate / meter.batch_size, 4))
                    if telemetry.enabled():
                        with telemetry.span("train.boundary.planes"):
                            # Memory gauges first so the snapshot emitted below
                            # carries this boundary's live-buffer/HBM readings (and
                            # the opt-state footprint ZeRO sharding divides). The
                            # census tags re-point at THIS boundary's state — the
                            # step donates its inputs, so last boundary's claims
                            # are dead weakrefs by now.
                            _memplane.tag("params", state.params)
                            _memplane.tag("opt_state", state.opt_state)
                            telemetry.sample_device_memory(
                                opt_state=state.opt_state)
                            telemetry.emit_metrics(global_step=step_i + 1)
                    if monitor is not None:
                        _observe_health(monitor, runner, step_i + 1,
                                        jax.device_get(pending_losses), state)
                        pending_losses = []
                    # Metric-history sample LAST at the boundary, so the sample
                    # (and the alert rules it evaluates) sees this period's
                    # attr/mfu/health/throughput gauges. An AlertHalt under
                    # AUTODIST_ALERT_ACTION=halt propagates from here — the
                    # train loop is the sampler a halt can actually stop — with
                    # the LIVE TrainState attached (the HealthHalt contract:
                    # a halt leaves the state checkpointable, not discarded).
                    try:
                        _history.maybe_sample(step_i + 1)
                    except telemetry.AlertHalt as e:
                        e.state = state
                        raise
                    # The boundary closed HEALTHY (no health anomaly raised, no
                    # alert fired past this point): this state is a valid
                    # rollback target. push() DEEP-COPIES on device via the
                    # ring's copy_fn — the step donates its input buffers, so a
                    # bare reference would be deleted by the next dispatch.
                    if ring is not None:
                        ring.push(step_i + 1, state)
                        if telemetry.enabled():
                            # Ring census: the deep-copied snapshot states are
                            # pinned device memory nothing else accounts for.
                            _memplane.tag("snapshots", ring.states())
                    if on_metrics is not None:
                        with telemetry.span("train.boundary.on_metrics"):
                            on_metrics(step_i + 1, float(loss), rate)
        if (eval_every and (step_i + 1) % eval_every == 0
                and not getattr(runner, "_is_remote_worker", False)):
            # Async remote workers skip: their local state is a compile-shapes
            # template and AsyncPSRunner.evaluate raises there by design. Sync
            # SPMD processes all evaluate together (the compiled eval is a
            # collective program).
            with telemetry.span("train.eval"):
                val = runner.evaluate(state, eval_batch, eval_fn)
            try:
                logging.info("train: step %d eval %.6f", step_i + 1, float(val))
            except (TypeError, ValueError):
                logging.info("train: step %d eval (pytree)", step_i + 1)
            if on_eval is not None:
                on_eval(step_i + 1, val)
        if (saver is not None and save_participant and save_every
                and (step_i + 1) % save_every == 0 and step_i + 1 < steps):
            with telemetry.span("train.checkpoint"):
                saver.save(state, prefix_base, runner=runner,
                           async_write=async_save)

    if monitor is not None and pending_losses:
        # End-of-run flush: a NaN in the final partial period (steps not a
        # multiple of log_every) must still anomaly/snapshot/halt — the
        # monitor's contract is EVERY step observed, not every full period.
        _observe_health(monitor, runner, steps,
                        jax.device_get(pending_losses), state)
    if meter is not None:
        meter.finish()   # freeze the run clock: average stays the TRAIN rate
    return state


def _boundary_fn(steps: int, save_every: int, eval_every: int):
    """``next_boundary(i)``: the first step index after ``i`` where a block
    must END (a ``save_every``/``eval_every`` multiple, or ``steps``) — ONE
    clipping rule, shared by the sync gather and the async block feed so
    their block shapes can never diverge."""
    boundaries = [p for p in (save_every, eval_every) if p]

    def next_boundary(i: int) -> int:
        nxt = steps
        for p in boundaries:
            nxt = min(nxt, (i // p + 1) * p)
        return nxt

    return next_boundary


class _BlockFeed:
    """The unrolled loop's async block source: a :class:`PrefetchProducer`
    whose pulls gather cadence-clipped host blocks (the sync ``gather``'s
    exact clipping, via the shared boundary fn) and whose transform is
    ``runner.shard_block`` — so block assembly AND host->HBM transfer run
    ``depth`` blocks ahead of the device. A source that exhausts mid-block
    still emits the partial block (the sync path's contract: those steps
    were consumed and must train)."""

    def __init__(self, runner, next_batch, batch_iter, start: int,
                 steps: int, unroll: int, next_boundary, depth: int,
                 workers: Optional[int] = None):
        self.first_batch = None   # meter sizing; set before the first emit
        self._next_batch = next_batch
        self._batch_iter = batch_iter
        self._cursor = start
        self._steps = steps
        self._unroll = unroll
        self._next_boundary = next_boundary
        self._exhausted = False
        self._producer = _prefetch.PrefetchProducer(
            self._pull, runner.shard_block, depth=depth,
            workers=workers or _prefetch.default_prefetch_workers(),
            name="train-feed")

    def _pull(self):
        i = self._cursor
        if self._exhausted or i >= self._steps:
            raise StopIteration
        blk = []
        for j in range(min(self._unroll, self._next_boundary(i) - i)):
            if self._next_batch is not None:
                blk.append(self._next_batch(i + j))
            else:
                try:
                    blk.append(next(self._batch_iter))
                except StopIteration:
                    self._exhausted = True
                    logging.info("train: batch iterator exhausted at "
                                 "step %d", i + len(blk))
                    break
        if not blk:
            raise StopIteration
        if self.first_batch is None:
            self.first_batch = blk[0]
        self._cursor = i + len(blk)
        return blk

    def next_block(self):
        """The next pre-sharded BatchBlock, or None at the end of the run
        (exhaustion / ``steps`` reached) — the sync ``gather``'s return
        contract."""
        try:
            return next(self._producer)
        except StopIteration:
            return None

    def queue_depth(self) -> int:
        return self._producer.queue_depth()

    def close(self):
        self._producer.close()


def _unrolled_loop(runner, state: TrainState, next_batch, batch_iter,
                   start: int, steps: int, unroll: int,
                   saver, prefix_base, save_participant, save_every: int,
                   async_save: bool, log_every: int, batch_size: Optional[int],
                   on_metrics, eval_every: int, eval_batch, eval_fn,
                   on_eval, monitor=None, feed: Optional[_BlockFeed] = None,
                   ring=None) -> TrainState:
    """The fused dispatch-ahead pipeline behind ``train(..., unroll=K)``.

    Consecutive batches are gathered into blocks of up to ``unroll`` steps and
    run as one compiled K-step scan (:meth:`DistributedRunner.run_many`);
    while the device executes a block, the host gathers and pre-shards the
    next one (a one-block dispatch-ahead queue — dispatch is asynchronous, so
    the prep overlaps device compute). Blocks are clipped so they END exactly
    at every ``save_every``/``eval_every`` multiple and at ``steps``, which
    keeps checkpoint/eval/resume semantics identical to the per-step loop;
    losses are read back (``jax.device_get``) only when a ``log_every``
    period closes at a block boundary.

    With ``feed`` (a :class:`_BlockFeed`, ``train(prefetch_depth>0)``) the
    blocks arrive pre-sharded from the async producer instead of being
    gathered here: ``train.data_wait`` then measures only the residual
    queue wait, and the producer's ``data.*`` telemetry carries the loader
    cost."""
    next_boundary = _boundary_fn(steps,
                                 save_every if saver is not None else 0,
                                 eval_every)
    exhausted = False
    first_batch = None

    def gather(i: int):
        """Up to min(unroll, steps-to-next-cadence-point) host batches
        starting at step index ``i``, pre-sharded; None when the run is
        over."""
        nonlocal exhausted, first_batch
        if feed is not None:
            with telemetry.span("train.data_wait"):
                block = feed.next_block()
            if first_batch is None:
                first_batch = feed.first_batch
            return block
        if exhausted or i >= steps:
            return None
        blk = []
        with telemetry.span("train.data_wait"):
            for j in range(min(unroll, next_boundary(i) - i)):
                if next_batch is not None:
                    blk.append(next_batch(i + j))
                else:
                    try:
                        blk.append(next(batch_iter))
                    except StopIteration:
                        exhausted = True
                        logging.info("train: batch iterator exhausted at "
                                     "step %d", i + len(blk))
                        break
        if not blk:
            return None
        if first_batch is None:
            first_batch = blk[0]
        with telemetry.span("runner.shard_block"):
            return runner.shard_block(blk)

    meter = None
    step_i = start
    # Health: the period's per-block loss stacks (device [K] arrays), read
    # back together at the boundary the meter already syncs.
    pending_losses = []
    block = gather(step_i)
    while block is not None:
        with telemetry.span("train.dispatch", steps=block.length):
            state, fetched = runner.run_many(state, block)
        losses = fetched[0] if isinstance(fetched, tuple) else fetched
        if monitor is not None:
            pending_losses.append(losses)
        step_i += block.length
        # Dispatch-ahead: run_many returns as soon as the K-step program is
        # enqueued; gather + pre-shard the next block NOW, before any sync
        # below, so host batch assembly and h->d transfer overlap the device.
        next_block = gather(step_i)
        queue_depth = (1 if next_block is not None else 0) \
            + (feed.queue_depth() if feed is not None else 0)
        if telemetry.enabled():
            telemetry.gauge("train.dispatch_queue_depth").set(queue_depth)
        if meter is None and log_every:
            meter = _make_meter(first_batch, batch_size, log_every)
        if meter is not None:
            rate = meter.step_many(block.length, sync=losses)
            if rate is not None:
                # From the meter's return to the end of the boundary block:
                # what the device waits for before the next feed. Its two
                # children are what only a traced run pays (planes) and the
                # caller's callback; the rest is what every run pays.
                with telemetry.span("train.boundary"):
                    # Attribution closes at the same boundary the meter synced
                    # (readback span recorded), before emit_metrics ships the
                    # snapshot carrying the freshly-booked attr/mfu gauges.
                    attr = _profiling.observe_period(step_i) \
                        if _profiling.active() else None
                    last = float(jax.device_get(losses)[-1])
                    # `q`: dispatch-ahead queue depth (0 means the host failed to
                    # stay ahead of the device — data-starved); `rb`: period
                    # seconds blocked on loss readback.
                    logging.info("train: step %d loss %.4f %.1f examples/s "
                                 "| q %d rb %.3fs%s",
                                 step_i, last, rate, queue_depth,
                                 meter.last_readback_s,
                                 _profiling.format_attr_line(attr))
                    # Steps/s gauge for the fleet console (same contract as the
                    # per-step loop: the registry carries the rate, not just
                    # the log line).
                    telemetry.gauge("train.steps_per_s").set(
                        round(rate / meter.batch_size, 4))
                    if telemetry.enabled():
                        with telemetry.span("train.boundary.planes"):
                            # Memory gauges first so the emitted snapshot carries
                            # this boundary's live-buffer/HBM readings (and the
                            # opt-state footprint ZeRO sharding divides); census
                            # tags re-pointed first, as in the per-step loop.
                            _memplane.tag("params", state.params)
                            _memplane.tag("opt_state", state.opt_state)
                            telemetry.sample_device_memory(
                                opt_state=state.opt_state)
                            telemetry.emit_metrics(global_step=step_i)
                    if monitor is not None:
                        flat = np.concatenate([np.asarray(l).reshape(-1) for l
                                               in jax.device_get(pending_losses)])
                        _observe_health(monitor, runner, step_i, flat, state)
                        pending_losses = []
                    # History sample last: the alert tick sees this boundary's
                    # freshly-booked gauges (AlertHalt propagates with the live
                    # state attached, like the per-step loop).
                    try:
                        _history.maybe_sample(step_i)
                    except telemetry.AlertHalt as e:
                        e.state = state
                        raise
                    # Healthy-boundary snapshot for the recover action (the
                    # per-step loop's contract: push() deep-copies on device to
                    # survive the step's buffer donation).
                    if ring is not None:
                        ring.push(step_i, state)
                        if telemetry.enabled():
                            _memplane.tag("snapshots", ring.states())
                    if on_metrics is not None:
                        with telemetry.span("train.boundary.on_metrics"):
                            on_metrics(step_i, last, rate)
        if eval_every and step_i % eval_every == 0:
            with telemetry.span("train.eval"):
                val = runner.evaluate(state, eval_batch, eval_fn)
            try:
                logging.info("train: step %d eval %.6f", step_i, float(val))
            except (TypeError, ValueError):
                logging.info("train: step %d eval (pytree)", step_i)
            if on_eval is not None:
                on_eval(step_i, val)
        if (saver is not None and save_participant and save_every
                and step_i % save_every == 0 and step_i < steps):
            with telemetry.span("train.checkpoint"):
                saver.save(state, prefix_base, runner=runner,
                           async_write=async_save)
        block = next_block
    if monitor is not None and pending_losses:
        # End-of-run flush (same contract as the per-step loop): the final
        # partial period's losses/bundle still reach the monitor.
        flat = np.concatenate([np.asarray(l).reshape(-1) for l
                               in jax.device_get(pending_losses)])
        _observe_health(monitor, runner, step_i, flat, state)
    if meter is not None:
        meter.finish()   # freeze the run clock: average stays the TRAIN rate
    return state
