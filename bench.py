"""Flagship benchmark: Transformer LM training throughput on the TPU.

Reproduces the reference's own measurement procedure (BASELINE.md): the lm1b
words/sec hook (``examples/lm1b/lm1b_train.py:64-74`` printed wps per 100 steps)
re-targeted at the flagship Transformer LM. Prints ONE JSON line:

    {"metric": ..., "value": N, "unit": "tokens/s", "device": {...}, "vs_baseline": N}

Without a TPU the flagship mode is an error (exit 1, ``"ok": false``): there is
no CPU shape fallback. ``chip_smoke.py`` builds the same trainer, and its
``run(TINY, require_tpu=False)`` is the CPU rehearsal.

The reference publishes no numeric table (figures only), so ``vs_baseline``
normalizes against the BASELINE.md procedural target: V100-class per-device lm1b
throughput, taken as 20k words/sec/device (the upper end of published LSTM-lm1b
single-V100 numbers; the north star is per-chip >= that).
"""

import argparse
import json
import math
import time

import numpy as np
from autodist_tpu.testing.sanitizer import san_lock

BASELINE_TOKENS_PER_SEC_PER_DEVICE = 20_000.0


def _baseline_path():
    import os
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "PERF_BASELINE.json")


def legacy_wire_send(sock, obj):
    """The pre-zero-copy transport send, verbatim: full encode to one bytes
    object, header CONCAT, one sendall. The reference implementation of
    'legacy framing' shared by :func:`wire_bench` and the interop tests
    (tests/test_codec_wire.py) so both always pin the same definition."""
    import struct

    from autodist_tpu.parallel import wire
    payload = wire.encode(obj)
    sock.sendall(struct.Struct("!Q").pack(len(payload)) + payload)


def legacy_wire_recv(sock):
    """The pre-zero-copy transport receive, verbatim: chunked accumulate into
    a bytearray, full-copy decode."""
    import struct

    from autodist_tpu.parallel import wire
    hdr = struct.Struct("!Q")

    def read_exact(n):
        buf = bytearray()
        while len(buf) < n:
            chunk = sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("peer closed")
            buf.extend(chunk)
        return bytes(buf)

    (n,) = hdr.unpack(read_exact(hdr.size))
    return wire.decode(read_exact(n))


def wire_bench(payload_mib: int = 40, rounds: int = 4):
    """PS-transport codec/framing micro-bench: round-trip a dense >=32 MiB
    parameter-style pytree over a loopback socketpair through (a) the legacy
    copying path — ``wire.encode`` + header-concat ``sendall`` + chunked
    accumulate receive + ``wire.decode(copy=True)`` — and (b) the zero-copy
    path the transport now ships: ``encode_parts`` borrowed buffers over
    ``sendmsg``, ``recv_into`` a recycled buffer, alias decode. Prints ONE
    JSON line with both throughputs and the speedup, diffed against the
    recorded ``ps_wire`` row in PERF_BASELINE.json. Pure host/CPU work (no
    accelerator): it isolates exactly the wire cost the async-PS data plane
    pays per step."""
    import socket
    import sys
    import threading

    from autodist_tpu.parallel import ps_transport as tp
    from autodist_tpu.parallel import wire

    rng = np.random.RandomState(0)
    n_layers = max(1, payload_mib // 4)
    tree = ("ok", {f"layer{i}": {"w": rng.randn(1024, 1024).astype(np.float32),
                                 "b": rng.randn(1024).astype(np.float32)}
                   for i in range(n_layers)}, None, 7)
    tree_bytes = sum(a.nbytes for lyr in tree[1].values() for a in lyr.values())

    legacy_send, legacy_recv = legacy_wire_send, legacy_wire_recv

    def zc_send(sock, obj):
        tp._send_payload(sock, wire.encode_parts(obj))

    def make_zc_recv():
        pool = tp._RecvBuffer()
        return lambda sock: tp._recv_msg(sock, pool=pool)[0]

    def measure(send_fn, recv_fn_factory):
        a, b = socket.socketpair()
        stop = []

        def echo():  # decode + re-encode each message, like a real endpoint
            recv_fn = recv_fn_factory()
            try:
                while not stop:
                    send_fn(b, recv_fn(b))
            except (ConnectionError, OSError):
                pass

        t = threading.Thread(target=echo, daemon=True)
        t.start()
        recv_fn = recv_fn_factory()
        try:
            send_fn(a, tree)   # warmup round-trip
            recv_fn(a)
            t0 = time.perf_counter()
            for _ in range(rounds):
                send_fn(a, tree)
                recv_fn(a)
            dt = time.perf_counter() - t0
        finally:
            stop.append(True)
            a.close()
            b.close()
        # Payload bytes crossing the wire per round trip: out + back.
        return 2 * tree_bytes * rounds / dt / 1e6

    legacy = measure(legacy_send, lambda: legacy_recv)
    zero_copy = measure(zc_send, make_zc_recv)
    result = {
        "metric": f"ps_wire round-trip ({tree_bytes / 2**20:.0f} MiB dense "
                  f"pytree, {n_layers} layers)",
        "unit": "MB/s",
        "rows": {"legacy": round(legacy, 1), "zero_copy": round(zero_copy, 1)},
        "speedup": round(zero_copy / legacy, 3),
    }
    try:
        with open(_baseline_path()) as f:
            recorded = json.load(f).get("ps_wire")
        if recorded:
            rec = recorded["speedup"]
            threshold = recorded.get("threshold_pct", 15.0)
            result["vs_recorded_speedup"] = round(result["speedup"] / rec, 4)
            if result["speedup"] < rec * (1.0 - threshold / 100.0):
                print(f"WARNING: ps_wire speedup {result['speedup']:.2f}x is "
                      f"more than {threshold}% below the recorded {rec:.2f}x "
                      f"— the zero-copy wire path regressed (see "
                      f"PERF_BASELINE.json ps_wire)", file=sys.stderr)
    except (OSError, KeyError, ValueError, TypeError, ZeroDivisionError):
        pass  # a missing/mangled snapshot must not break the bench
    print(json.dumps(result))
    return result


def telemetry_overhead(steps: int = 150):
    """Telemetry cost micro-bench (CPU micro-model, host-dispatch-bound — the
    same shape class as the unroll sweep's CPU leg, so step time is dominated
    by exactly the host path the spans instrument):

    - steps/s through ``runner.run`` with telemetry DISABLED (production
      default) and ENABLED (span ring + registry recording),
    - the disabled span construct's direct cost in ns (1e5 no-op
      ``with telemetry.span(...)`` blocks), and the implied
      ``disabled_overhead_pct`` — span cost x spans-per-step as a fraction of
      the measured step time. This is the gated number: the recorded
      ``telemetry_overhead`` row in PERF_BASELINE.json carries
      ``max_disabled_overhead_pct`` (2.0), and exceeding it means the
      disabled fast path stopped being a single attribute check.

    The steps/s pair is cross-checked against the recorded rows only on a
    matching platform (absolute CPU rates are machine-specific); the span-ns
    gate is machine-relative by construction so it gates everywhere."""
    import sys

    import jax
    import jax.numpy as jnp
    import optax

    from autodist_tpu import AutoDist, telemetry
    from autodist_tpu.models import transformer_lm
    from autodist_tpu.strategy import AllReduce

    platform = jax.devices()[0].platform
    n_dev = len(jax.devices())
    cfg = transformer_lm.TransformerLMConfig(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_len=64, dtype=jnp.float32, tied_output=False)
    batch_size, seq_len = 8 * n_dev, 16
    model, params = transformer_lm.init_params(cfg)
    loss_fn = transformer_lm.make_loss_fn(model)
    batch = transformer_lm.synthetic_batch(cfg, batch_size=batch_size,
                                           seq_len=seq_len)
    ad = AutoDist(strategy_builder=AllReduce())
    runner = ad.create_distributed_session(loss_fn, params, optax.adam(1e-3),
                                           example_batch=batch)
    state = runner.init(params)

    def measure(n):
        nonlocal state
        loss = None
        t0 = time.perf_counter()
        for _ in range(n):
            state, loss = runner.run(state, batch)
        _ = jax.device_get(loss)   # completion fence
        return n / (time.perf_counter() - t0)

    was_enabled = telemetry.enabled()
    telemetry.disable()
    measure(10)                    # compile + warmup
    rate_disabled = measure(steps)
    telemetry.enable()
    measure(3)
    rate_enabled = measure(steps)
    telemetry.clear()

    # Direct disabled-construct cost, independent of host-load noise in the
    # steps/s pair: N no-op spans, ns each.
    telemetry.disable()
    n_spans = 100_000
    t0 = time.perf_counter_ns()
    for _ in range(n_spans):
        with telemetry.span("bench"):
            pass
    span_ns = (time.perf_counter_ns() - t0) / n_spans
    if was_enabled:
        telemetry.enable()

    # Spans per step on the instrumented per-step train path: train.data_wait,
    # train.dispatch, runner.shard_batch, runner.run.dispatch, plus headroom
    # for the meter's boundary readback and async-PS client spans.
    spans_per_step = 8
    step_ns = 1e9 / rate_disabled
    disabled_overhead_pct = 100.0 * span_ns * spans_per_step / step_ns

    result = {
        "metric": f"telemetry_overhead ({platform} x{n_dev}, d{cfg.d_model}"
                  f"x{cfg.n_layers}, seq{seq_len}, bs{batch_size})",
        "unit": "steps/s",
        "rows": {"disabled": round(rate_disabled, 2),
                 "enabled": round(rate_enabled, 2)},
        "enabled_vs_disabled": round(rate_enabled / rate_disabled, 4),
        "disabled_span_ns": round(span_ns, 1),
        "spans_per_step": spans_per_step,
        "disabled_overhead_pct": round(disabled_overhead_pct, 4),
    }
    try:
        with open(_baseline_path()) as f:
            recorded = json.load(f).get("telemetry_overhead")
        if recorded:
            max_pct = recorded.get("max_disabled_overhead_pct", 2.0)
            if disabled_overhead_pct > max_pct:
                print(f"WARNING: disabled-mode telemetry overhead "
                      f"{disabled_overhead_pct:.3f}% of step time exceeds the "
                      f"{max_pct}% gate — the disabled span fast path "
                      f"regressed (see PERF_BASELINE.json "
                      f"telemetry_overhead)", file=sys.stderr)
            floor = recorded.get("enabled_vs_disabled_floor")
            if (floor and recorded.get("platform") == platform
                    and result["enabled_vs_disabled"] < floor):
                print(f"WARNING: enabled-telemetry steps/s is "
                      f"{result['enabled_vs_disabled']:.2f}x the disabled "
                      f"rate, below the recorded {floor:.2f}x floor — "
                      f"enabled-mode recording got costlier (see "
                      f"PERF_BASELINE.json telemetry_overhead)",
                      file=sys.stderr)
    except (OSError, KeyError, ValueError, TypeError, ZeroDivisionError):
        pass  # a missing/mangled snapshot must not break the bench
    print(json.dumps(result))
    return result


def health_overhead(steps: int = 60, rounds: int = 3):
    """Training-health monitor cost micro-bench (the CPU transformer
    micro-model at a training-shaped batch):

    - ``bundle_ms`` — the DIRECT cost of the fused numerics bundle
      (``telemetry.health.device_bundle`` jitted over the model's own
      param-shaped trees, min of ``rounds`` timed loops), and the implied
      ``overhead_pct`` = bundle time as a fraction of the measured
      monitors-DISABLED step time. This is the gated number: the recorded
      ``health_overhead`` row in PERF_BASELINE.json carries
      ``max_overhead_pct`` (2.0) — the bundle growing past ~2% of a
      host-bound step means it stopped being a few fused reductions (the
      same machine-relative construction as the telemetry row's span-ns
      gate, so it gates everywhere).
    - the steps/s pair through ``runner.run`` with monitors disabled vs
      enabled (best of ``rounds`` interleaved rounds, the enabled side
      paying a real monitor boundary each round) — cross-checked against
      the recorded ratio floor only on a matching platform: absolute
      steps/s pairs are load-noisy on shared boxes, so the ratio floor is
      a wide backstop against gross fusion/donation regressions, not the
      primary gate.
    """
    import sys

    import jax
    import jax.numpy as jnp
    import optax

    from autodist_tpu import AutoDist, telemetry
    from autodist_tpu.models import transformer_lm
    from autodist_tpu.strategy import AllReduce
    from autodist_tpu.telemetry import health as _health

    platform = jax.devices()[0].platform
    n_dev = len(jax.devices())
    cfg = transformer_lm.TransformerLMConfig(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_len=64, dtype=jnp.float32, tied_output=False)
    # A training-shaped batch (not the dispatch-stress micro shape): the
    # bundle's cost is O(params) and independent of the batch, so the gate
    # ratio must be taken against a step doing a real batch's work.
    batch_size, seq_len = 32 * n_dev, 32
    model, params = transformer_lm.init_params(cfg)
    loss_fn = transformer_lm.make_loss_fn(model)
    batch = transformer_lm.synthetic_batch(cfg, batch_size=batch_size,
                                           seq_len=seq_len)

    def build(health: bool):
        ad = AutoDist(strategy_builder=AllReduce())
        runner = ad.create_distributed_session(
            loss_fn, params, optax.adam(1e-3), example_batch=batch,
            health=health)
        return runner, runner.init(params)

    monitor = _health.HealthMonitor(_health.HealthConfig(action="warn"))
    runners = {False: build(False), True: build(True)}

    def measure(health: bool, n: int) -> float:
        runner, state = runners[health]
        loss = None
        t0 = time.perf_counter()
        for _ in range(n):
            state, loss = runner.run(state, batch)
        if health:
            # The boundary work a real train() period pays: one bundle
            # readback + the host-side monitor pass (inside the timed
            # window, so the pair covers the WHOLE enabled cost; the
            # device_get doubles as the completion fence).
            monitor.observe(n, [float(jax.device_get(loss))],
                            jax.device_get(runner.last_health))
        else:
            _ = jax.device_get(loss)   # completion fence
        dt = time.perf_counter() - t0
        runners[health] = (runner, state)
        return n / dt

    measure(False, 5)   # compile + warmup both programs
    measure(True, 5)
    best = {False: 0.0, True: 0.0}
    for _ in range(rounds):            # interleaved: load noise hits both
        best[False] = max(best[False], measure(False, steps))
        best[True] = max(best[True], measure(True, steps))
    telemetry.clear()

    # Direct bundle cost on the model's own tree shapes (min-of-rounds —
    # load spikes stretch a round, never shrink one).
    tree = runners[True][1].params
    bundle_fn = jax.jit(_health.device_bundle)
    out = bundle_fn(tree, tree, tree, jnp.float32(1.0))
    jax.block_until_ready(out)
    bundle_ms = math.inf
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(100):
            out = bundle_fn(tree, tree, tree, jnp.float32(1.0))
        jax.block_until_ready(out)
        bundle_ms = min(bundle_ms, (time.perf_counter() - t0) * 10.0)
    step_ms = 1e3 / best[False]
    overhead_pct = 100.0 * bundle_ms / step_ms

    result = {
        "metric": f"health_overhead ({platform} x{n_dev}, d{cfg.d_model}"
                  f"x{cfg.n_layers}, seq{seq_len}, bs{batch_size})",
        "unit": "steps/s",
        "rows": {"disabled": round(best[False], 2),
                 "enabled": round(best[True], 2)},
        "enabled_vs_disabled": round(best[True] / best[False], 4),
        "bundle_ms": round(bundle_ms, 4),
        "overhead_pct": round(overhead_pct, 3),
    }
    try:
        with open(_baseline_path()) as f:
            recorded = json.load(f).get("health_overhead")
        if recorded:
            max_pct = recorded.get("max_overhead_pct", 2.0)
            if overhead_pct > max_pct:
                print(f"WARNING: the fused health bundle costs "
                      f"{overhead_pct:.2f}% of a host-bound step, above the "
                      f"{max_pct}% gate — it grew beyond a few fused "
                      f"reductions (see PERF_BASELINE.json health_overhead)",
                      file=sys.stderr)
            floor = recorded.get("enabled_vs_disabled_floor")
            if (floor and recorded.get("platform") == platform
                    and result["enabled_vs_disabled"] < floor):
                print(f"WARNING: health-enabled steps/s is "
                      f"{result['enabled_vs_disabled']:.2f}x the disabled "
                      f"rate, below the recorded {floor:.2f}x floor — "
                      f"enabled-mode monitoring got costlier (see "
                      f"PERF_BASELINE.json health_overhead)",
                      file=sys.stderr)
    except (OSError, KeyError, ValueError, TypeError, ZeroDivisionError):
        pass  # a missing/mangled snapshot must not break the bench
    print(json.dumps(result))
    return result


def attr_overhead(steps: int = 120, log_every: int = 40, rounds: int = 3):
    """Performance-attribution plane cost micro-bench (the CPU transformer
    micro-model, host-dispatch-bound — the shape class where per-dispatch
    overhead is most visible):

    - steps/s through ``runner.run`` with the attribution plane DISABLED
      (production default: telemetry fully off) and ENABLED
      (``profiling.enable()`` — span ring + per-dispatch signature/cost
      accounting + a real ``observe_period`` boundary per round), best of
      ``rounds`` interleaved rounds;
    - the DIRECT enabled-side costs, machine-relative so they gate
      everywhere: ``note_ns`` (one per-dispatch signature count) and
      ``observe_ms`` (one log-boundary attribution pass over a
      ``log_every``-step period's spans), combined as ``overhead_pct`` =
      (note_ns + observe_ms/log_every) over the measured disabled step
      time. This is the gated number: the ``attr_overhead`` row in
      PERF_BASELINE.json carries ``max_overhead_pct`` (2.0) — attribution
      growing past ~2%% of a host-bound step means the boundary join
      stopped being a columnar-ring scan.

    With ``AUTODIST_PROFILE_DIR`` set, the enabled run's profile JSON is
    written there (the ci.sh adprof self-diff smoke reads it)."""
    import sys

    import jax
    import jax.numpy as jnp
    import optax

    from autodist_tpu import AutoDist, telemetry
    from autodist_tpu.models import transformer_lm
    from autodist_tpu.strategy import AllReduce
    from autodist_tpu.telemetry import profiling

    platform = jax.devices()[0].platform
    n_dev = len(jax.devices())
    cfg = transformer_lm.TransformerLMConfig(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_len=64, dtype=jnp.float32, tied_output=False)
    batch_size, seq_len = 8 * n_dev, 16
    model, params = transformer_lm.init_params(cfg)
    loss_fn = transformer_lm.make_loss_fn(model)
    batch = transformer_lm.synthetic_batch(cfg, batch_size=batch_size,
                                           seq_len=seq_len)
    ad = AutoDist(strategy_builder=AllReduce())
    runner = ad.create_distributed_session(loss_fn, params, optax.adam(1e-3),
                                           example_batch=batch)
    state = runner.init(params)

    def measure(n, boundary=False):
        nonlocal state
        loss = None
        t0 = time.perf_counter()
        for _ in range(n):
            state, loss = runner.run(state, batch)
        _ = jax.device_get(loss)   # completion fence
        if boundary:
            # The boundary work a real train() period pays, inside the
            # timed window so the pair covers the WHOLE enabled cost.
            profiling.observe_period()
        return n / (time.perf_counter() - t0)

    was_enabled = telemetry.enabled()
    telemetry.disable()
    profiling.disable()
    measure(10)                    # compile + warmup
    profiling.enable()             # also enables spans
    profiling.reset()
    measure(3, boundary=True)
    profiling.disable()
    telemetry.disable()
    best = {"disabled": 0.0, "enabled": 0.0}
    for _ in range(rounds):        # interleaved: load noise hits both sides
        best["disabled"] = max(best["disabled"], measure(steps))
        profiling.enable()
        best["enabled"] = max(best["enabled"], measure(steps, boundary=True))
        profiling.disable()
        telemetry.disable()

    # Direct boundary cost: a log_every-step period's spans, one
    # observe_period pass (min of rounds — load stretches, never shrinks).
    profiling.enable()
    observe_ms = math.inf
    for _ in range(rounds):
        measure(log_every)
        t0 = time.perf_counter()
        rec = profiling.observe_period()
        observe_ms = min(observe_ms, (time.perf_counter() - t0) * 1e3)
    shares = rec["shares"] if rec else None
    profile_path = profiling.maybe_write_profile()

    # Direct per-dispatch cost of the signature count.
    n_notes = 100_000
    t0 = time.perf_counter_ns()
    for _ in range(n_notes):
        profiling.note_dispatch("bench-sig", "step", 1)
    note_ns = (time.perf_counter_ns() - t0) / n_notes
    profiling.reset()
    profiling.disable()
    telemetry.clear()
    if was_enabled:
        telemetry.enable()
    else:
        telemetry.disable()

    step_ns = 1e9 / best["disabled"]
    overhead_pct = 100.0 * (note_ns + observe_ms * 1e6 / log_every) / step_ns

    result = {
        "metric": f"attr_overhead ({platform} x{n_dev}, d{cfg.d_model}"
                  f"x{cfg.n_layers}, seq{seq_len}, bs{batch_size}, "
                  f"log_every {log_every})",
        "unit": "steps/s",
        "rows": {"disabled": round(best["disabled"], 2),
                 "enabled": round(best["enabled"], 2)},
        "enabled_vs_disabled": round(best["enabled"] / best["disabled"], 4),
        "note_ns": round(note_ns, 1),
        "observe_ms": round(observe_ms, 4),
        "overhead_pct": round(overhead_pct, 4),
        "attr": shares,
    }
    if profile_path:
        result["profile"] = profile_path
    try:
        with open(_baseline_path()) as f:
            recorded = json.load(f).get("attr_overhead")
        if recorded:
            max_pct = recorded.get("max_overhead_pct", 2.0)
            if overhead_pct > max_pct:
                print(f"WARNING: the attribution plane costs "
                      f"{overhead_pct:.3f}% of a host-bound step, above the "
                      f"{max_pct}% gate — per-dispatch counting or the "
                      f"boundary span join got costlier (see "
                      f"PERF_BASELINE.json attr_overhead)", file=sys.stderr)
            floor = recorded.get("enabled_vs_disabled_floor")
            if (floor and recorded.get("platform") == platform
                    and result["enabled_vs_disabled"] < floor):
                print(f"WARNING: attribution-enabled steps/s is "
                      f"{result['enabled_vs_disabled']:.2f}x the disabled "
                      f"rate, below the recorded {floor:.2f}x floor (see "
                      f"PERF_BASELINE.json attr_overhead)", file=sys.stderr)
    except (OSError, KeyError, ValueError, TypeError, ZeroDivisionError):
        pass  # a missing/mangled snapshot must not break the bench
    print(json.dumps(result))
    return result


def mem_overhead(steps: int = 120, log_every: int = 40, rounds: int = 3):
    """Memory-plane cost micro-bench (the CPU transformer micro-model,
    host-dispatch-bound — where any per-boundary cost is most visible):

    - steps/s through ``runner.run`` with the plane IDLE (no claims, no
      attribution — the production default) and ARMED (the train loop's
      boundary work: re-tag params + opt_state census claims and one
      ``sample_device_memory`` pass, whose attribution decomposes the live
      bytes over the claims and books ``mem.owned.*`` + ``mem.pressure``),
      best of ``rounds`` interleaved rounds;
    - the DIRECT armed-side costs, machine-relative so they gate
      everywhere: ``tag_ms`` (one params + opt_state re-tag — tree walk +
      weakref registration) and ``sample_ms`` (one full
      ``sample_device_memory`` with the attribution pass), combined as
      ``overhead_pct`` = (tag_ms + sample_ms) / log_every over the
      measured idle step time. The gated number: the ``mem_overhead`` row
      in PERF_BASELINE.json carries ``max_overhead_pct`` (2.0) — the
      census growing past ~2% of a host-bound step means attribution
      stopped being one live-array walk over a handful of claims.
    """
    import sys

    import jax
    import jax.numpy as jnp
    import optax

    from autodist_tpu import AutoDist, telemetry
    from autodist_tpu.models import transformer_lm
    from autodist_tpu.strategy import AllReduce
    from autodist_tpu.telemetry import memplane

    platform = jax.devices()[0].platform
    n_dev = len(jax.devices())
    cfg = transformer_lm.TransformerLMConfig(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_len=64, dtype=jnp.float32, tied_output=False)
    batch_size, seq_len = 8 * n_dev, 16
    model, params = transformer_lm.init_params(cfg)
    loss_fn = transformer_lm.make_loss_fn(model)
    batch = transformer_lm.synthetic_batch(cfg, batch_size=batch_size,
                                           seq_len=seq_len)
    ad = AutoDist(strategy_builder=AllReduce())
    runner = ad.create_distributed_session(loss_fn, params, optax.adam(1e-3),
                                           example_batch=batch)
    state = runner.init(params)

    def measure(n, boundary=False):
        nonlocal state
        loss = None
        t0 = time.perf_counter()
        for i in range(n):
            state, loss = runner.run(state, batch)
            if boundary and (i + 1) % log_every == 0:
                # The boundary work an armed train() period pays, at the
                # period rate, inside the timed window: re-point the
                # census claims at this boundary's (donation-fresh) state
                # and run the sampler whose attribution pass walks them.
                memplane.tag("params", state.params)
                memplane.tag("opt_state", state.opt_state)
                telemetry.sample_device_memory(opt_state=state.opt_state)
        _ = jax.device_get(loss)   # completion fence
        return n / (time.perf_counter() - t0)

    try:
        measure(10)                         # compile + warmup
        measure(log_every, boundary=True)   # warm the boundary path too
        best = {"disabled": 0.0, "enabled": 0.0}
        for _ in range(rounds):    # interleaved: load noise hits both sides
            best["disabled"] = max(best["disabled"], measure(steps))
            best["enabled"] = max(best["enabled"],
                                  measure(steps, boundary=True))

        # Direct boundary costs (min of rounds — load stretches, never
        # shrinks).
        tag_ms = sample_ms = math.inf
        for _ in range(rounds):
            t0 = time.perf_counter()
            memplane.tag("params", state.params)
            memplane.tag("opt_state", state.opt_state)
            tag_ms = min(tag_ms, (time.perf_counter() - t0) * 1e3)
            t0 = time.perf_counter()
            telemetry.sample_device_memory(opt_state=state.opt_state)
            sample_ms = min(sample_ms, (time.perf_counter() - t0) * 1e3)
        census = memplane.census()
    finally:
        memplane.reset()

    step_ms = 1e3 / best["disabled"]
    overhead_pct = 100.0 * (tag_ms + sample_ms) / log_every / step_ms

    result = {
        "metric": f"mem_overhead ({platform} x{n_dev}, d{cfg.d_model}"
                  f"x{cfg.n_layers}, seq{seq_len}, bs{batch_size}, "
                  f"log_every {log_every})",
        "unit": "steps/s",
        "rows": {"disabled": round(best["disabled"], 2),
                 "enabled": round(best["enabled"], 2)},
        "enabled_vs_disabled": round(best["enabled"] / best["disabled"], 4),
        "tag_ms": round(tag_ms, 4),
        "sample_ms": round(sample_ms, 4),
        "owners": sorted(census),
        "overhead_pct": round(overhead_pct, 4),
    }
    try:
        with open(_baseline_path()) as f:
            recorded = json.load(f).get("mem_overhead")
        if recorded:
            max_pct = recorded.get("max_overhead_pct", 2.0)
            if overhead_pct > max_pct:
                print(f"WARNING: the memory plane costs "
                      f"{overhead_pct:.3f}% of a host-bound step, above the "
                      f"{max_pct}% gate — census tagging or the attribution "
                      f"pass got costlier (see PERF_BASELINE.json "
                      f"mem_overhead)", file=sys.stderr)
            floor = recorded.get("enabled_vs_disabled_floor")
            if (floor and recorded.get("platform") == platform
                    and result["enabled_vs_disabled"] < floor):
                print(f"WARNING: census-armed steps/s is "
                      f"{result['enabled_vs_disabled']:.2f}x the idle "
                      f"rate, below the recorded {floor:.2f}x floor (see "
                      f"PERF_BASELINE.json mem_overhead)",
                      file=sys.stderr)
    except (OSError, KeyError, ValueError, TypeError, ZeroDivisionError):
        pass  # a missing/mangled snapshot must not break the bench
    print(json.dumps(result))
    return result


def metrics_overhead(steps: int = 120, log_every: int = 40, rounds: int = 3):
    """Fleet-metrics-plane cost micro-bench (the CPU transformer micro-model,
    host-dispatch-bound — where any per-boundary cost is most visible):

    - steps/s through ``runner.run`` with the plane DISABLED (production
      default: no history, no alerting) and ENABLED (a MetricsHistory with
      JSONL shards + the SHIPPED alert rule set sampling at every
      ``log_every`` boundary, plus one OpenMetrics render per boundary —
      the worst case of a scraper polling exactly at boundary rate), best
      of ``rounds`` interleaved rounds;
    - the DIRECT enabled-side costs, machine-relative so they gate
      everywhere: ``sample_ms`` (one registry snapshot + ring append +
      shard line + full default-rule alert evaluation) and ``render_ms``
      (one exposition render of the populated registry), combined as
      ``overhead_pct`` = (sample_ms + render_ms) / log_every over the
      measured disabled step time. This is the gated number: the
      ``metrics_overhead`` row in PERF_BASELINE.json carries
      ``max_overhead_pct`` (2.0) — the plane growing past ~2% of a
      host-bound step means sampling stopped being one snapshot walk.
    """
    import shutil
    import sys
    import tempfile

    import jax
    import jax.numpy as jnp
    import optax

    from autodist_tpu import AutoDist
    from autodist_tpu.models import transformer_lm
    from autodist_tpu.strategy import AllReduce
    from autodist_tpu.telemetry import alerts, history, openmetrics

    platform = jax.devices()[0].platform
    n_dev = len(jax.devices())
    cfg = transformer_lm.TransformerLMConfig(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_len=64, dtype=jnp.float32, tied_output=False)
    batch_size, seq_len = 8 * n_dev, 16
    model, params = transformer_lm.init_params(cfg)
    loss_fn = transformer_lm.make_loss_fn(model)
    batch = transformer_lm.synthetic_batch(cfg, batch_size=batch_size,
                                           seq_len=seq_len)
    ad = AutoDist(strategy_builder=AllReduce())
    runner = ad.create_distributed_session(loss_fn, params, optax.adam(1e-3),
                                           example_batch=batch)
    state = runner.init(params)

    tmp = tempfile.mkdtemp(prefix="metrics_bench_")
    engine = alerts.AlertEngine(rules=alerts.load_rules(""), action="warn")
    hist = history.MetricsHistory(out_dir=tmp, min_interval_s=0.0,
                                  engine=engine)

    def measure(n, boundary=False):
        nonlocal state
        loss = None
        t0 = time.perf_counter()
        for i in range(n):
            state, loss = runner.run(state, batch)
            if boundary and (i + 1) % log_every == 0:
                # The boundary work a real armed train() period pays, AT
                # the period rate — sample (+ alert tick + shard line) and
                # one scrape-rate render per log_every steps, inside the
                # timed window so the pair covers the WHOLE enabled cost.
                hist.sample(step=i + 1)
                openmetrics.render()
        _ = jax.device_get(loss)   # completion fence
        return n / (time.perf_counter() - t0)

    try:
        measure(10)                    # compile + warmup
        measure(log_every, boundary=True)   # warm the boundary path too
        best = {"disabled": 0.0, "enabled": 0.0}
        for _ in range(rounds):    # interleaved: load noise hits both sides
            best["disabled"] = max(best["disabled"], measure(steps))
            best["enabled"] = max(best["enabled"],
                                  measure(steps, boundary=True))

        # Direct boundary costs (min of rounds — load stretches, never
        # shrinks).
        sample_ms = render_ms = math.inf
        for _ in range(rounds):
            t0 = time.perf_counter()
            hist.sample()
            sample_ms = min(sample_ms, (time.perf_counter() - t0) * 1e3)
            t0 = time.perf_counter()
            text = openmetrics.render()
            render_ms = min(render_ms, (time.perf_counter() - t0) * 1e3)
        n_shards = len(hist.shards())
    finally:
        hist.close()
        shutil.rmtree(tmp, ignore_errors=True)   # CI runs this every pass

    step_ms = 1e3 / best["disabled"]
    overhead_pct = 100.0 * (sample_ms + render_ms) / log_every / step_ms

    result = {
        "metric": f"metrics_overhead ({platform} x{n_dev}, d{cfg.d_model}"
                  f"x{cfg.n_layers}, seq{seq_len}, bs{batch_size}, "
                  f"log_every {log_every})",
        "unit": "steps/s",
        "rows": {"disabled": round(best["disabled"], 2),
                 "enabled": round(best["enabled"], 2)},
        "enabled_vs_disabled": round(best["enabled"] / best["disabled"], 4),
        "sample_ms": round(sample_ms, 4),
        "render_ms": round(render_ms, 4),
        "render_bytes": len(text),
        "rules": len(engine.rules),
        "shards": n_shards,
        "overhead_pct": round(overhead_pct, 4),
    }
    try:
        with open(_baseline_path()) as f:
            recorded = json.load(f).get("metrics_overhead")
        if recorded:
            max_pct = recorded.get("max_overhead_pct", 2.0)
            if overhead_pct > max_pct:
                print(f"WARNING: the fleet metrics plane costs "
                      f"{overhead_pct:.3f}% of a host-bound step, above the "
                      f"{max_pct}% gate — history sampling or the exporter "
                      f"render got costlier (see PERF_BASELINE.json "
                      f"metrics_overhead)", file=sys.stderr)
            floor = recorded.get("enabled_vs_disabled_floor")
            if (floor and recorded.get("platform") == platform
                    and result["enabled_vs_disabled"] < floor):
                print(f"WARNING: metrics-enabled steps/s is "
                      f"{result['enabled_vs_disabled']:.2f}x the disabled "
                      f"rate, below the recorded {floor:.2f}x floor (see "
                      f"PERF_BASELINE.json metrics_overhead)",
                      file=sys.stderr)
    except (OSError, KeyError, ValueError, TypeError, ZeroDivisionError):
        pass  # a missing/mangled snapshot must not break the bench
    print(json.dumps(result))
    return result


def trace_pull_overhead(rounds: int = 5):
    """Cluster-trace pull cost micro-bench: fill the span ring to its full
    capacity (AUTODIST_TELEMETRY_RING, default 65536 spans) and measure

    - ``stall_ms`` — the CHIEF-SIDE blocking work of serving one ``trace``
      opcode: columnar ring snapshot (``telemetry.local_trace_state``) +
      zero-copy wire encode. This is the piece that competes with training
      for the chief's GIL/CPU, so it is the gated number: the recorded
      ``trace_pull`` row in PERF_BASELINE.json carries ``max_stall_ms``
      (50.0) — a full-ring pull must never stall training longer than that.
    - ``pull_ms`` — a worker's full round-trip (request, snapshot, encode,
      loopback socket, alias decode) against a real PSServer over a
      numpy-only stub runner, for the end-to-end picture.

    Pure host/CPU work; the columnar blob layout (name/tid tables + ndarray
    columns instead of 65536 per-span tuples) is exactly what this bench
    exists to defend."""
    import sys

    from autodist_tpu import const, telemetry
    from autodist_tpu.parallel import wire

    cap = int(const.ENV.AUTODIST_TELEMETRY_RING.val)
    was_enabled = telemetry.enabled()
    telemetry.enable()
    telemetry.clear()
    for i in range(cap):
        # Every 8th span carries args: realistic rings are mostly bare spans
        # with occasional annotated ones.
        if i & 7:
            with telemetry.span("bench.fill"):
                pass
        else:
            with telemetry.span("bench.fill", step=i):
                pass

    # Chief-side blocking cost: snapshot + encode (what the serving thread
    # does while training shares the process). MIN across rounds: the
    # intrinsic cost is what the gate defends; host-load spikes on a shared
    # CI box are not trace-plane regressions.
    stall_samples = []
    blob_bytes = 0
    for _ in range(max(rounds, 7)):
        t0 = time.perf_counter()
        state = telemetry.local_trace_state()
        parts = wire.encode_parts(("ok", state))
        stall_samples.append((time.perf_counter() - t0) * 1e3)
        blob_bytes = sum(len(p) for p in parts)
    stall_ms = min(stall_samples)

    # End-to-end loopback pull through a real PSServer.
    class _StubPSRunner:
        def __init__(self):
            from autodist_tpu.parallel.staleness import (ParameterService,
                                                         StalenessController)
            from autodist_tpu.runner import TrainState
            state = TrainState(step=np.zeros((), np.int32),
                               params={"w": np.ones((8,), np.float32)},
                               opt_state=(), ef_state=())
            self.service = ParameterService(state, lambda s, g: s)
            self.controller = StalenessController(1, staleness=1)

        def add_worker(self, worker_id=None, with_generation=False):
            wid, gen = self.controller.register_with_generation(worker_id)
            handle = type("H", (), {"worker_id": wid})()
            return (handle, gen) if with_generation else handle

    from autodist_tpu.parallel.ps_transport import PSServer, RemotePSWorker
    server = PSServer(_StubPSRunner(), host="127.0.0.1", watchdog=False)
    remote = RemotePSWorker("%s:%d" % server.address, runner=None,
                            worker_id=0, overlap=False)
    try:
        remote.trace()                      # warmup
        pull_samples = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            blob = remote.trace()
            pull_samples.append((time.perf_counter() - t0) * 1e3)
        n_spans = len(blob["name_idx"])
    finally:
        remote.close()
        server.close()
        telemetry.clear()
        if not was_enabled:
            telemetry.disable()
    pull_ms = sorted(pull_samples)[len(pull_samples) // 2]

    result = {
        "metric": f"trace_pull ({n_spans}-span ring, "
                  f"{blob_bytes / 2**20:.2f} MiB blob)",
        "unit": "ms",
        "rows": {"stall_ms": round(stall_ms, 2), "pull_ms": round(pull_ms, 2)},
        "ring": n_spans,
    }
    try:
        with open(_baseline_path()) as f:
            recorded = json.load(f).get("trace_pull")
        if recorded:
            max_stall = recorded.get("max_stall_ms", 50.0)
            if stall_ms > max_stall:
                print(f"WARNING: full-ring trace snapshot+encode took "
                      f"{stall_ms:.1f}ms, over the {max_stall}ms stall gate — "
                      f"a trace pull would stall training (see "
                      f"PERF_BASELINE.json trace_pull; did the columnar blob "
                      f"layout regress to per-span encoding?)",
                      file=sys.stderr)
    except (OSError, KeyError, ValueError, TypeError, ZeroDivisionError):
        pass  # a missing/mangled snapshot must not break the bench
    print(json.dumps(result))
    return result


def reqtrace_overhead(requests: int = 24, clients: int = 4):
    """Request-trace plane cost bench (the serving analogue of
    --telemetry-overhead):

    - requests/s at a fixed offered load through a real 1-replica
      Router + RouterServer fleet with the request-trace ring DISARMED
      (production default) and ARMED (``AUTODIST_REQTRACE=1``: lifecycle
      marks at every hop plus the wire trace token on each forwarded
      generate),
    - the disarmed ``reqtrace.mark`` direct cost in ns (1e5 calls — the
      one-attribute-read contract) and the armed per-mark cost, and
    - the implied ``armed_overhead_pct``: armed mark cost x the marks the
      fleet actually booked per request (counted from the ring, so new
      instrumentation sites raise the bill automatically) as a fraction of
      the measured mean request latency. This is the gated number — the
      ``reqtrace_overhead`` row in PERF_BASELINE.json carries
      ``max_overhead_pct`` (2.0), and exceeding it means tracing a request
      stopped being a handful of deque appends.

    The rps pair is cross-checked against the recorded
    ``armed_vs_disarmed_floor`` only as a wide backstop — closed-loop
    loopback serving on a shared CPU box is noisy — so the
    machine-relative direct-cost percentage is the hard gate."""
    import sys
    import threading

    import jax
    import jax.numpy as jnp

    from autodist_tpu import serving
    from autodist_tpu.models import transformer_lm
    from autodist_tpu.serving.router import Router, RouterServer
    from autodist_tpu.telemetry import reqtrace

    platform = jax.devices()[0].platform
    cfg = transformer_lm.TransformerLMConfig(
        vocab_size=256, d_model=64, n_heads=2, n_layers=2, d_ff=256,
        max_len=128, dtype=jnp.float32)
    model, params = transformer_lm.init_params(cfg)

    def replica_factory():
        scfg = serving.ServeConfig(max_batch=4, temperature=0.0)
        batcher = serving.Batcher(
            serving.LMEngine(model, params, scfg), scfg)
        return serving.InferenceServer(batcher)

    def offered_load(router_server, n, max_new):
        ok, errors = [], []
        lock = san_lock()

        def client_thread(wid):
            c = serving.ServeClient(router_server.address)
            try:
                for i in range(wid, n, clients):
                    try:
                        prompt = np.arange(1, 9, dtype=np.int32) + i % 40
                        tokens, _ = c.generate(prompt, max_new, seed=i)
                        with lock:
                            ok.append(tokens)
                    except serving.ServeError as e:
                        with lock:
                            errors.append(str(e))
            finally:
                c.close()

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client_thread, args=(w,))
                   for w in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return ok, errors, time.perf_counter() - t0

    was_armed = reqtrace.enabled()
    reqtrace.disable()
    walls = {}
    router = Router(replica_factory, n_replicas=1, start=False)
    server = RouterServer(router)
    try:
        for rep in router.replicas():      # compile off the clock
            warm = serving.ServeClient(rep.address)
            try:
                warm.generate(np.arange(1, 9, dtype=np.int32), 2)
            finally:
                warm.close()
        for mode in ("disarmed", "armed"):
            if mode == "armed":
                reqtrace.enable()
                reqtrace.clear()
            ok, errors, wall = offered_load(server, requests, 8)
            if errors or len(ok) != requests:
                raise RuntimeError(
                    f"reqtrace bench ({mode}): {len(ok)}/{requests} ok, "
                    f"errors: {errors[:3]}")
            walls[mode] = wall
        marks_per_request = len(reqtrace.snapshot_marks()) / requests
    finally:
        server.close()
        reqtrace.clear()
        reqtrace.disable()

    # Direct per-mark costs, independent of loopback-serving noise: N marks
    # each way, ns per call. The disarmed number IS the one-attribute-read
    # contract; the armed number prices the intern lookup + deque appends.
    n_marks = 100_000
    t0 = time.perf_counter_ns()
    for _ in range(n_marks):
        reqtrace.mark("bench", "queued")
    disarmed_mark_ns = (time.perf_counter_ns() - t0) / n_marks
    reqtrace.enable()
    t0 = time.perf_counter_ns()
    for _ in range(n_marks):
        reqtrace.mark("bench", "queued")
    armed_mark_ns = (time.perf_counter_ns() - t0) / n_marks
    reqtrace.clear()
    if not was_armed:
        reqtrace.disable()

    # clients closed-loop threads are busy for the whole wall, so total
    # request-seconds ~= wall x clients and the mean latency follows.
    request_ns = walls["armed"] * clients / requests * 1e9
    armed_overhead_pct = 100.0 * armed_mark_ns * marks_per_request / request_ns

    result = {
        "metric": f"reqtrace_overhead ({platform}, 1-replica fleet, "
                  f"{requests} req x {clients} clients)",
        "unit": "req/s",
        "rows": {"disarmed": round(requests / walls["disarmed"], 2),
                 "armed": round(requests / walls["armed"], 2)},
        "armed_vs_disarmed": round(walls["disarmed"] / walls["armed"], 4),
        "disarmed_mark_ns": round(disarmed_mark_ns, 1),
        "armed_mark_ns": round(armed_mark_ns, 1),
        "marks_per_request": round(marks_per_request, 1),
        "armed_overhead_pct": round(armed_overhead_pct, 4),
    }
    try:
        with open(_baseline_path()) as f:
            recorded = json.load(f).get("reqtrace_overhead")
        if recorded:
            max_pct = recorded.get("max_overhead_pct", 2.0)
            if armed_overhead_pct > max_pct:
                print(f"WARNING: armed request-trace overhead "
                      f"{armed_overhead_pct:.3f}% of request latency exceeds "
                      f"the {max_pct}% gate — a lifecycle mark stopped being "
                      f"a handful of deque appends (see PERF_BASELINE.json "
                      f"reqtrace_overhead)", file=sys.stderr)
            floor = recorded.get("armed_vs_disarmed_floor")
            if (floor and recorded.get("platform") == platform
                    and result["armed_vs_disarmed"] < floor):
                print(f"WARNING: armed-reqtrace req/s is "
                      f"{result['armed_vs_disarmed']:.2f}x the disarmed "
                      f"rate, below the recorded {floor:.2f}x floor — armed "
                      f"recording got costlier on the serving path (see "
                      f"PERF_BASELINE.json reqtrace_overhead)",
                      file=sys.stderr)
    except (OSError, KeyError, ValueError, TypeError, ZeroDivisionError):
        pass  # a missing/mangled snapshot must not break the bench
    print(json.dumps(result))
    return result


def zero_update_bench(steps: int = 60, dp: int = 2):
    """ZeRO weight-update sharding (arXiv 2004.13336) memory/step bench.

    Runs the CPU micro-model (same shape class as the other micro-benches)
    twice on a dp-device mesh — ``zero=0`` (replicated optimizer update,
    today's default) and ``zero=1`` (reduce-scatter -> shard-local update ->
    all-gather) — and reports:

    - ``opt_bytes``: per-device resident optimizer-state bytes
      (``telemetry.opt_state_bytes`` — max over devices of the shard bytes
      each holds), unsharded vs sharded. The GATED number is their ratio:
      the recorded ``zero_update`` row carries ``min_opt_bytes_ratio``
      (1.5 at dp=2; the ideal is ~dp, less the replicated scalar leaves),
      and falling below it means the plan stopped sharding the moments.
    - ``steps_s``: steps/s for both runs (informational — on CPU the
      collectives the constraint points insert are host work, so sharded is
      expected to cost a few percent; on real pods the reduce-scatter is
      cheaper than the all-reduce it replaces).
    - ``live_bytes``: per-device resident bytes over ALL live arrays after
      each run (max over devices of the shard bytes each holds — the same
      accounting as the PR 5 ``device.live_bytes`` gauge family).
      Informational only: on the CPU backend the dispatch device also holds
      tracing/executable residue (a params-sized constant copy survives
      session construction), which blurs whole-process accounting — the
      clean, gated signal is ``opt_bytes``.

    Needs >= dp local devices: when the host exposes fewer (plain
    ``python bench.py --zero`` on a 1-CPU box), dp CPU devices are simulated
    via XLA_FLAGS before the backend initializes — which is why this must
    run before any other jax-touching bench in the same process."""
    import os
    import sys

    if "jax" not in sys.modules:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={dp}").strip()
    import jax
    import jax.numpy as jnp
    import optax

    from autodist_tpu import AutoDist, telemetry
    from autodist_tpu.models import transformer_lm
    from autodist_tpu.strategy import AllReduce

    platform = jax.devices()[0].platform
    n_dev = len(jax.devices())
    if n_dev < dp:
        print(json.dumps({"metric": "zero_update", "skipped":
                          f"needs >= {dp} devices, found {n_dev} (jax was "
                          f"already initialized before --zero could simulate "
                          f"them)"}))
        return None
    cfg = transformer_lm.TransformerLMConfig(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_len=64, dtype=jnp.float32, tied_output=False)
    batch_size, seq_len = 8 * n_dev, 16
    model, params = transformer_lm.init_params(cfg)
    loss_fn = transformer_lm.make_loss_fn(model)
    batch = transformer_lm.synthetic_batch(cfg, batch_size=batch_size,
                                           seq_len=seq_len)
    # Source params live on the host: a device-0 jnp copy would sit in
    # jax.live_arrays() across both runs and dominate the per-device max.
    params = jax.tree_util.tree_map(np.asarray, params)

    def measure(zero):
        ad = AutoDist(strategy_builder=AllReduce())
        runner = ad.create_distributed_session(
            loss_fn, params, optax.adam(1e-3), example_batch=batch, zero=zero)
        state = runner.init(params)
        opt_bytes = telemetry.opt_state_bytes(state.opt_state)
        loss = None
        for _ in range(5):          # compile + warmup
            state, loss = runner.run(state, batch)
        _ = jax.device_get(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            state, loss = runner.run(state, batch)
        _ = jax.device_get(loss)
        rate = steps / (time.perf_counter() - t0)
        # Per-device resident bytes (a sharded array's global .nbytes would
        # count every shard on every device and hide the saving). Collect
        # first: the previous run's donated-buffer cycles otherwise linger
        # in jax.live_arrays() and mask the difference.
        import gc
        gc.collect()
        live = telemetry.opt_state_bytes(jax.live_arrays())
        del state
        return opt_bytes, rate, live

    bytes_plain, rate_plain, live_plain = measure(0)
    bytes_zero, rate_zero, live_zero = measure(1)
    ratio = bytes_plain / max(1, bytes_zero)

    result = {
        "metric": f"zero_update ({platform} x{n_dev}, d{cfg.d_model}"
                  f"x{cfg.n_layers}, seq{seq_len}, bs{batch_size}, adam)",
        "unit": "bytes/device",
        "rows": {"opt_bytes_unsharded": bytes_plain,
                 "opt_bytes_sharded": bytes_zero},
        "opt_bytes_ratio": round(ratio, 3),
        "steps_s": {"unsharded": round(rate_plain, 2),
                    "sharded": round(rate_zero, 2)},
        "live_bytes": {"unsharded": live_plain, "sharded": live_zero},
    }
    try:
        with open(_baseline_path()) as f:
            recorded = json.load(f).get("zero_update")
        if recorded:
            floor = recorded.get("min_opt_bytes_ratio", 1.5)
            if ratio < floor:
                print(f"WARNING: ZeRO opt-state per-device bytes ratio "
                      f"{ratio:.2f}x is below the {floor:.2f}x gate at "
                      f"dp={n_dev} — weight-update sharding stopped dividing "
                      f"the optimizer state (see PERF_BASELINE.json "
                      f"zero_update)", file=sys.stderr)
    except (OSError, KeyError, ValueError, TypeError, ZeroDivisionError):
        pass  # a missing/mangled snapshot must not break the bench
    print(json.dumps(result))
    return result


def serve_bench(requests: int = 32, clients: int = 8, max_batch: int = 4):
    """Serving-plane bench: loopback requests/s and p99 total latency at a
    fixed offered load, STATIC vs CONTINUOUS batching on the tiny LM.

    One shared :class:`~autodist_tpu.serving.runtime.LMEngine` (so both modes
    pay the same compiled programs and the same per-step device cost) is
    driven through a real :class:`~autodist_tpu.serving.InferenceServer` by
    ``clients`` closed-loop client threads — each its own connection, the
    subsystem's intended concurrency model. The workload alternates short and
    long generations (8 vs 48 new tokens), the mix that exposes the convoy
    effect: a static wave drains at the pace of its longest member while
    freed slots sit idle, whereas continuous admission refills them between
    decode steps. The GATE (recorded ``serving`` row in PERF_BASELINE.json)
    is that continuous batching beats static on requests/s at
    equal-or-better p99 — the property the whole batcher design exists for.
    Each mode is measured over 3 interleaved rounds and the best round is
    reported (the same best-of-N discipline the unroll/telemetry benches use
    on this load-noisy box class — decode-step counts, not host scheduling
    luck, are what the gate compares). Greedy decode, CPU-safe, no
    accelerator required."""
    import sys
    import threading

    import jax.numpy as jnp

    from autodist_tpu import serving
    from autodist_tpu.models import transformer_lm

    import jax
    platform = jax.devices()[0].platform

    cfg = transformer_lm.TransformerLMConfig(
        vocab_size=256, d_model=64, n_heads=2, n_layers=2, d_ff=256,
        max_len=128, dtype=jnp.float32)
    model, params = transformer_lm.init_params(cfg)
    scfg = serving.ServeConfig(max_batch=max_batch, temperature=0.0)
    engine = serving.LMEngine(model, params, scfg)

    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, size=int(rng.randint(4, 48)))
               .astype(np.int32) for _ in range(requests)]
    # Long generations dominate wall time: a static wave of 4 costs its
    # longest member's 48 steps while freed slots idle; continuous refills
    # them, so it runs ~len(mix)/fill fewer decode dispatches.
    max_new = [8 if i % 2 == 0 else 48 for i in range(requests)]

    def measure(mode):
        import dataclasses
        batcher = serving.Batcher(
            engine, dataclasses.replace(scfg, mode=mode))
        server = serving.InferenceServer(batcher)
        timings, errors = [], []
        lock = san_lock()

        def client_thread(wid):
            c = serving.ServeClient(server.address)
            try:
                for i in range(wid, requests, clients):
                    try:
                        _, timing = c.generate(prompts[i], max_new[i], seed=i)
                        with lock:
                            timings.append(timing)
                    except serving.ServeError as e:
                        with lock:
                            errors.append(str(e))
            finally:
                c.close()

        # Warm every jitted program off the clock (one prefill per touched
        # bucket + decode + insert) through the full transport path.
        warm = serving.ServeClient(server.address)
        try:
            for b in sorted({serving.bucket_for(len(p), engine.buckets)
                             for p in prompts}):
                warm.generate(np.arange(1, 1 + b, dtype=np.int32), 2)
        finally:
            warm.close()

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client_thread, args=(w,))
                   for w in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        server.close()
        if errors or len(timings) != requests:
            raise RuntimeError(
                f"serve bench ({mode}): {len(timings)}/{requests} ok, "
                f"errors: {errors[:3]}")
        totals_ms = sorted(t["total_s"] * 1e3 for t in timings)
        p99 = totals_ms[min(len(totals_ms) - 1,
                            int(round(0.99 * (len(totals_ms) - 1))))]
        return round(requests / wall, 2), round(p99, 1)

    # 3 interleaved rounds per mode; the best round each (max rps, min p99)
    # is the gated pair — load spikes on a shared box hit one round, not
    # both modes' best.
    static_runs, cont_runs = [], []
    for _ in range(3):
        static_runs.append(measure("static"))
        cont_runs.append(measure("continuous"))
    static_rps = max(r for r, _ in static_runs)
    static_p99 = min(p for _, p in static_runs)
    cont_rps = max(r for r, _ in cont_runs)
    cont_p99 = min(p for _, p in cont_runs)

    result = {
        "metric": f"serving ({platform}, d{cfg.d_model}x{cfg.n_layers}, "
                  f"{max_batch} slots, {clients} clients, {requests} reqs, "
                  f"8/48-token mix, best of 3)",
        "unit": "requests/s",
        "rows": {"static_rps": static_rps, "continuous_rps": cont_rps,
                 "static_p99_ms": static_p99, "continuous_p99_ms": cont_p99},
        "rps_ratio": round(cont_rps / max(1e-9, static_rps), 3),
        "p99_ratio": round(cont_p99 / max(1e-9, static_p99), 3),
    }
    try:
        with open(_baseline_path()) as f:
            recorded = json.load(f).get("serving")
        if recorded:
            min_rps = recorded.get("min_rps_ratio", 1.0)
            max_p99 = recorded.get("max_p99_ratio", 1.0)
            if result["rps_ratio"] < min_rps:
                print(f"WARNING: continuous batching throughput is "
                      f"{result['rps_ratio']:.2f}x static — below the "
                      f"{min_rps:.2f}x gate; decode-step admission stopped "
                      f"paying for itself (see PERF_BASELINE.json serving)",
                      file=sys.stderr)
            if result["p99_ratio"] > max_p99:
                print(f"WARNING: continuous batching p99 is "
                      f"{result['p99_ratio']:.2f}x static — above the "
                      f"{max_p99:.2f}x gate; early-exit slot reuse stopped "
                      f"improving tail latency (see PERF_BASELINE.json "
                      f"serving)", file=sys.stderr)
    except (OSError, KeyError, ValueError, TypeError, ZeroDivisionError):
        pass  # a missing/mangled snapshot must not break the bench
    print(json.dumps(result))
    return result


def serve_fleet_bench(requests: int = 24, fleet_requests: int = 16,
                      clients: int = 4):
    """Fleet-serving bench (PR 17): three legs, gated against the
    ``serve_fleet`` row in PERF_BASELINE.json.

    1. PAGED vs DENSE concurrency at the SAME KV HBM budget. The dense
       engine owns ``4 x max_len`` slot-rows; the paged engine owns the same
       token count as pages (plus the scratch page) and admits on RESERVABLE
       PAGES, so short requests pack ``>= min_concurrency_ratio`` times more
       concurrent work into the identical memory. Both engines serve the
       identical request set through a real Batcher and the gate REQUIRES
       bit-identical token streams — the capacity win is worthless if the
       math changed (this is a RuntimeError, not a warning).
    2. Router 2-replica vs 1-replica offered-load rps through a real
       RouterServer + unchanged ServeClients. On a shared-core CPU box the
       replicas contend for the same host, so the recorded floor is a wide
       "adding a replica must not collapse throughput" guard, not a 2x pin
       (on real fleets each replica owns its chips).
    3. Kill-a-replica: ``clients`` closed-loop clients against a 2-replica
       fleet; one replica is killed with requests IN FLIGHT. The contract
       (RuntimeError on violation, same discipline as the selfheal bench):
       every request completes, ZERO client-visible failures, and the
       recovery plane books >= 1 respawn — the router replayed the severed
       requests (same rid, replica-side dedup) onto the survivor and healed
       the fleet."""
    import sys
    import threading

    import jax.numpy as jnp

    from autodist_tpu import serving
    from autodist_tpu.models import transformer_lm
    from autodist_tpu.parallel import recovery as _recovery
    from autodist_tpu.serving.router import Router, RouterServer

    import jax
    platform = jax.devices()[0].platform

    cfg = transformer_lm.TransformerLMConfig(
        vocab_size=256, d_model=64, n_heads=2, n_layers=2, d_ff=256,
        max_len=128, dtype=jnp.float32)
    model, params = transformer_lm.init_params(cfg)

    # ---- leg 1: paged vs dense concurrency at equal KV HBM -------------
    # Dense: 4 slots x 128 tokens = 512 KV rows. Paged: 32 usable 16-token
    # pages = the same 512 rows (+1 scratch page), but a 2-page request
    # only OCCUPIES 2 pages, so 16 of them run concurrently.
    dense_cfg = serving.ServeConfig(max_batch=4, temperature=0.0)
    paged_cfg = serving.ServeConfig(max_batch=16, temperature=0.0,
                                    page_len=16, kv_pages=33)
    rng = np.random.RandomState(0)
    workload = [(rng.randint(1, cfg.vocab_size,
                             size=int(rng.randint(6, 15))).astype(np.int32),
                 12, i) for i in range(requests)]

    def run_engine(engine, scfg):
        batcher = serving.Batcher(engine, scfg, start=False)
        reqs = [batcher.submit(p, n, seed=s) for p, n, s in workload]
        peak = 0
        for _ in range(4000):
            if all(r.done.is_set() for r in reqs):
                break
            batcher.run_once()
            peak = max(peak, len(batcher.in_flight_snapshot()))
        bad = [r.error for r in reqs if r.error or not r.done.is_set()]
        if bad:
            raise RuntimeError(f"serve-fleet bench: engine leg failed: "
                               f"{bad[:3]}")
        return [tuple(r.tokens) for r in reqs], peak

    dense_tokens, dense_peak = run_engine(
        serving.LMEngine(model, params, dense_cfg), dense_cfg)
    paged_tokens, paged_peak = run_engine(
        serving.PagedLMEngine(model, params, paged_cfg), paged_cfg)
    if paged_tokens != dense_tokens:
        raise RuntimeError(
            "serve-fleet bench: paged tokens diverged from dense — the "
            "paged KV cache broke bit-identity (see serving/paged.py)")
    concurrency_ratio = round(paged_peak / max(1, dense_peak), 3)

    # ---- legs 2+3: router fleet rps and kill-a-replica -----------------
    def replica_factory():
        scfg = serving.ServeConfig(max_batch=4, temperature=0.0)
        batcher = serving.Batcher(
            serving.LMEngine(model, params, scfg), scfg)
        return serving.InferenceServer(batcher)

    def offered_load(router_server, n, max_new):
        ok, errors = [], []
        lock = san_lock()

        def client_thread(wid):
            c = serving.ServeClient(router_server.address)
            try:
                for i in range(wid, n, clients):
                    try:
                        prompt = np.arange(1, 9, dtype=np.int32) + i % 40
                        tokens, _ = c.generate(prompt, max_new, seed=i)
                        with lock:
                            ok.append(tokens)
                    except serving.ServeError as e:
                        with lock:
                            errors.append(str(e))
            finally:
                c.close()

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client_thread, args=(w,))
                   for w in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return ok, errors, time.perf_counter() - t0

    fleet_rps = {}
    for n_replicas in (1, 2):
        router = Router(replica_factory, n_replicas=n_replicas, start=False)
        server = RouterServer(router)
        try:
            # Warm EVERY replica's programs off the clock, addressed
            # directly — the router's least-loaded tie-break would send
            # every idle sequential warm to replica 0 and leave the
            # others to compile on the clock.
            for rep in router.replicas():
                warm = serving.ServeClient(rep.address)
                try:
                    warm.generate(np.arange(1, 9, dtype=np.int32), 2)
                finally:
                    warm.close()
            ok, errors, wall = offered_load(server, fleet_requests, 8)
            if errors or len(ok) != fleet_requests:
                raise RuntimeError(
                    f"serve-fleet bench ({n_replicas} replica(s)): "
                    f"{len(ok)}/{fleet_requests} ok, errors: {errors[:3]}")
            fleet_rps[n_replicas] = round(fleet_requests / wall, 2)
        finally:
            server.close()
    fleet_ratio = round(fleet_rps[2] / max(1e-9, fleet_rps[1]), 3)

    # Kill leg: requests in flight, one replica dies, nobody notices.
    _recovery.reset()
    old_backoff = Router.RESPAWN_BACKOFF_S
    Router.RESPAWN_BACKOFF_S = 0.05
    try:
        router = Router(replica_factory, n_replicas=2, start=False)
        server = RouterServer(router)
        try:
            for rep in router.replicas():
                warm = serving.ServeClient(rep.address)
                try:
                    warm.generate(np.arange(1, 9, dtype=np.int32), 2)
                finally:
                    warm.close()
            victim = router.replicas()[0]

            def killer():
                deadline = time.monotonic() + 10.0
                while victim.load() == 0 and time.monotonic() < deadline:
                    time.sleep(0.001)
                victim.server.kill()

            kt = threading.Thread(target=killer, name="bench-fleet-killer")
            kt.start()
            try:
                ok, errors, _ = offered_load(server, fleet_requests, 24)
            finally:
                # join unconditionally: a failed load leg used to leak the
                # non-daemon killer past the bench (thread-fence finding)
                kt.join(timeout=15.0)
            counts = _recovery.recovery_snapshot()["counts"]
            if errors or len(ok) != fleet_requests:
                raise RuntimeError(
                    f"serve-fleet bench (kill leg): {len(ok)}/"
                    f"{fleet_requests} completed, errors: {errors[:3]} — "
                    f"a replica death leaked to clients")
            if counts.get("respawns", 0) < 1:
                raise RuntimeError(
                    "serve-fleet bench (kill leg): no respawn booked — the "
                    "kill never landed mid-flight; the leg proved nothing")
        finally:
            server.close()
    finally:
        Router.RESPAWN_BACKOFF_S = old_backoff

    result = {
        "metric": f"serve_fleet ({platform}, d{cfg.d_model}x{cfg.n_layers}, "
                  f"dense 4x{cfg.max_len} vs paged 32x16 pages, "
                  f"{clients} clients)",
        "rows": {"dense_peak": dense_peak, "paged_peak": paged_peak,
                 "fleet1_rps": fleet_rps[1], "fleet2_rps": fleet_rps[2]},
        "concurrency_ratio": concurrency_ratio,
        "fleet_rps_ratio": fleet_ratio,
        "kill_leg": {"completed": len(ok), "respawns": counts["respawns"],
                     "evicted": counts["evicted"]},
    }
    try:
        with open(_baseline_path()) as f:
            recorded = json.load(f).get("serve_fleet")
        if recorded:
            floor = recorded.get("min_concurrency_ratio", 1.5)
            if concurrency_ratio < floor:
                print(f"WARNING: paged concurrency is "
                      f"{concurrency_ratio:.2f}x dense at equal KV HBM — "
                      f"below the {floor:.2f}x gate; page packing stopped "
                      f"paying for itself (see PERF_BASELINE.json "
                      f"serve_fleet)", file=sys.stderr)
            rps_floor = recorded.get("min_fleet_rps_ratio", 0.5)
            if fleet_ratio < rps_floor:
                print(f"WARNING: 2-replica rps is {fleet_ratio:.2f}x "
                      f"1-replica — below the {rps_floor:.2f}x guard; "
                      f"routing overhead is eating the fleet (see "
                      f"PERF_BASELINE.json serve_fleet)", file=sys.stderr)
    except (OSError, KeyError, ValueError, TypeError, ZeroDivisionError):
        pass  # a missing/mangled snapshot must not break the bench
    print(json.dumps(result))
    return result


def unroll_sweep(factors):
    """Measure the fused multi-step path (``runner.run_many``) at each unroll
    factor and print ONE JSON line with the steps/s curve.

    On accelerators this uses the flagship model (accum off — the sweep
    isolates dispatch amortization); on CPU a tiny model whose step is
    host-dispatch-bound, so the curve measures exactly the overhead ``unroll``
    amortizes, not chip throughput. The curve is diffed against the recorded
    ``unroll_curve`` in PERF_BASELINE.json when the platform matches: the
    gate metric is the max-factor SPEEDUP over unroll=1 (machine-relative, so
    it transfers across hosts of the same platform class better than raw
    rates)."""
    import jax
    import jax.numpy as jnp
    import optax

    from autodist_tpu import AutoDist
    from autodist_tpu.models import transformer_lm
    from autodist_tpu.ops import mosaic_compiles
    from autodist_tpu.strategy import AllReduce

    platform = jax.devices()[0].platform
    n_dev = len(jax.devices())
    on_accel = platform != "cpu"
    if on_accel:
        cfg = transformer_lm.TransformerLMConfig(
            vocab_size=32_000, d_model=512, n_heads=8, n_layers=6, d_ff=2048,
            max_len=512, dtype=jnp.bfloat16, tied_output=False,
            fused_head=mosaic_compiles())
        batch_size, seq_len, total_steps = 384 * n_dev, 256, 160
    else:
        cfg = transformer_lm.TransformerLMConfig(
            vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=128,
            max_len=64, dtype=jnp.float32, tied_output=False)
        batch_size, seq_len, total_steps = 8 * n_dev, 16, 192

    model, params = transformer_lm.init_params(cfg)
    loss_fn = transformer_lm.make_loss_fn(model)
    batch = transformer_lm.synthetic_batch(cfg, batch_size=batch_size,
                                           seq_len=seq_len)
    ad = AutoDist(strategy_builder=AllReduce())
    runner = ad.create_distributed_session(loss_fn, params, optax.adam(1e-3),
                                           example_batch=batch)
    state = runner.init(params)

    rows = {}
    for k in factors:
        block = runner.shard_block([batch] * k)
        state, losses = runner.run_many(state, block)   # compile + warmup
        _ = jax.device_get(losses)
        n_blocks = max(3, total_steps // k)
        t0 = time.perf_counter()
        for _ in range(n_blocks):
            state, losses = runner.run_many(state, block)
        _ = jax.device_get(losses)   # completion fence (see main())
        dt = time.perf_counter() - t0
        rows[str(k)] = round(n_blocks * k / dt, 2)

    result = {
        "metric": f"unroll_sweep ({platform} x{n_dev}, d{cfg.d_model}"
                  f"x{cfg.n_layers}, seq{seq_len}, bs{batch_size})",
        "unit": "steps/s",
        "rows": rows,
        "tokens_per_step": batch_size * seq_len,
    }
    if "1" in rows:
        # The gate metric is the MAX factor's speedup (the factor the recorded
        # baseline was measured at), so a regression confined to the deepest
        # unroll cannot hide behind a healthy shallower factor; best_factor
        # stays informational (the argmax-rate factor).
        max_f = max(int(f) for f in rows)
        result["best_factor"] = max((int(f) for f in rows),
                                    key=lambda f: rows[str(f)])
        result["speedup_vs_unroll1"] = round(rows[str(max_f)] / rows["1"], 4)
        try:
            import sys
            with open(_baseline_path()) as f:
                recorded = json.load(f).get("unroll_curve")
            if recorded and recorded.get("platform") == platform:
                rec_speedup = recorded["speedup_vs_unroll1"]
                threshold = recorded.get("threshold_pct", 5.0)
                result["vs_recorded_speedup"] = round(
                    result["speedup_vs_unroll1"] / rec_speedup, 4)
                if result["speedup_vs_unroll1"] < \
                        rec_speedup * (1.0 - threshold / 100.0):
                    print(f"WARNING: unroll speedup "
                          f"{result['speedup_vs_unroll1']:.2f}x is more than "
                          f"{threshold}% below the recorded "
                          f"{rec_speedup:.2f}x — the fused multi-step path "
                          f"regressed (see PERF_BASELINE.json unroll_curve)",
                          file=sys.stderr)
        except (OSError, KeyError, ValueError, TypeError, ZeroDivisionError):
            pass  # a missing/mangled snapshot must not break the bench
    print(json.dumps(result))
    return result


def autotune_bench(rounds: int = 3, steps: int = 48):
    """Plan-autotuner gate: tuned plan vs default plan steps/s on the CPU
    micro-model (the host-dispatch-bound shape class where the knob space —
    unroll amortization above all — has real headroom).

    Runs one full predict-prune-probe search (``strategy.autotune``) with a
    throwaway plan cache, then measures the DEFAULT plan (the session's
    PSLoadBalancing builder, ``unroll=1``) and the TUNED winner back-to-back
    (best of ``rounds`` interleaved rounds, ~``steps`` optimizer steps each,
    through the tuner's shared probe loop so both sides pay identical
    harness cost). Gated numbers in the PERF_BASELINE.json ``autotune``
    row:

    - ``tuned_vs_default`` >= ``min_ratio`` (1.0): the searched plan must
      never lose to the default it replaces;
    - ``probed`` <= ``top_k``: stage-1 pruning must hold — at most top-k of
      the enumerated candidates get measured probe steps (the search-cost
      contract; ``search_s`` reports the wall cost)."""
    import sys
    import tempfile

    import jax
    import jax.numpy as jnp
    import optax

    from autodist_tpu import const
    from autodist_tpu.models import transformer_lm
    from autodist_tpu.strategy import PSLoadBalancing
    from autodist_tpu.strategy.autotune import autotune
    from autodist_tpu.strategy.tuner import measure_candidate

    platform = jax.devices()[0].platform
    n_dev = len(jax.devices())
    cfg = transformer_lm.TransformerLMConfig(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_len=64, dtype=jnp.float32, tied_output=False)
    batch_size, seq_len = 8 * n_dev, 16
    model, params = transformer_lm.init_params(cfg)
    loss_fn = transformer_lm.make_loss_fn(model)
    batch = transformer_lm.synthetic_batch(cfg, batch_size=batch_size,
                                           seq_len=seq_len)

    top_k = int(const.ENV.AUTODIST_TUNE_TOPK.val)
    with tempfile.TemporaryDirectory() as tmp:
        plan = autotune(loss_fn, params, optax.adam(1e-3), batch,
                        plan_cache=f"{tmp}/plan_cache.json",
                        warmup_steps=2, measure_steps=6)

    def measure(builder, unroll, zero, accum):
        n = max(4, steps // unroll)
        r = measure_candidate(builder, loss_fn, params, optax.adam(1e-3),
                              batch, warmup_steps=2, measure_steps=n,
                              unroll=unroll, zero=zero,
                              accumulation_steps=accum)
        return r.steps_per_sec or 0.0

    best = {"default": 0.0, "tuned": 0.0}
    for _ in range(rounds):   # interleaved: load noise hits both sides
        best["default"] = max(best["default"],
                              measure(PSLoadBalancing(), 1, 0, 1))
        best["tuned"] = max(best["tuned"],
                            measure(plan.make_builder(), plan.unroll,
                                    plan.zero, plan.accumulation_steps))

    ratio = best["tuned"] / best["default"] if best["default"] else 0.0
    result = {
        "metric": f"autotune ({platform} x{n_dev}, d{cfg.d_model}"
                  f"x{cfg.n_layers}, seq{seq_len}, bs{batch_size})",
        "unit": "steps/s",
        "rows": {"default": round(best["default"], 2),
                 "tuned": round(best["tuned"], 2)},
        "tuned_vs_default": round(ratio, 4),
        "plan": plan.name,
        "predicted_step_ms": round((plan.predicted or {}).get("step_s", 0.0)
                                   * 1e3, 4),
        "search_s": round(plan.search_s, 2),
        "enumerated": plan.enumerated,
        "probed": plan.probed,
        "top_k": top_k,
    }
    if plan.probed > top_k:
        print(f"WARNING: autotune measured-probed {plan.probed} candidates, "
              f"above top_k={top_k} — stage-1 pruning stopped bounding the "
              f"search cost (see strategy/autotune.py)", file=sys.stderr)
    try:
        with open(_baseline_path()) as f:
            recorded = json.load(f).get("autotune")
        if recorded and recorded.get("platform") == platform:
            floor = recorded.get("min_ratio", 1.0)
            if ratio < floor:
                print(f"WARNING: tuned plan is {ratio:.2f}x the default "
                      f"plan's steps/s, below the {floor:.2f}x floor — the "
                      f"autotuner picked a losing plan (see "
                      f"PERF_BASELINE.json autotune)", file=sys.stderr)
    except (OSError, KeyError, ValueError, TypeError, ZeroDivisionError):
        pass  # a missing/mangled snapshot must not break the bench
    print(json.dumps(result))
    return result


def data_plane_bench(steps: int = 96, log_every: int = 32, rounds: int = 3,
                     sleep_ms: float = 4.0, depth: int = 4):
    """Input-data plane gate: an injected slow host loader (a fixed
    per-batch sleep — the MLPerf pod bottleneck in miniature) fed to
    ``train()`` synchronously (``prefetch_depth=0``) vs through the async
    prefetch producer (``prefetch_depth=depth``), best of ``rounds``
    interleaved rounds. Gated numbers in the PERF_BASELINE.json
    ``data_plane`` row:

    - ``prefetch_vs_sync`` >= ``min_ratio`` (1.2): the producer must
      actually hide the injected stall behind the running step;
    - the prefetched leg's ``train.attr.data_wait`` share must sit BELOW
      ``max_data_wait_share`` — the shipped ``data_wait_drift`` alert's
      band, so the rule that pages on a sync slow loader stays quiet on
      the prefetched one;
    - ``data.producer_wait`` must still carry >= half the injected loader
      seconds: hiding the stall must not hide the SLOW LOADER (the
      counter is how attribution keeps naming it);
    - the two legs' final params must be BIT-IDENTICAL (prefetching
      reorders nothing — same batches, same math, same order)."""
    import sys

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from autodist_tpu import AutoDist, telemetry, training
    from autodist_tpu.models import transformer_lm
    from autodist_tpu.strategy import AllReduce
    from autodist_tpu.telemetry import alerts, profiling

    platform = jax.devices()[0].platform
    n_dev = len(jax.devices())
    cfg = transformer_lm.TransformerLMConfig(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_len=64, dtype=jnp.float32, tied_output=False)
    batch_size, seq_len = 8 * n_dev, 16
    model, params = transformer_lm.init_params(cfg)
    loss_fn = transformer_lm.make_loss_fn(model)
    uniques = [transformer_lm.synthetic_batch(cfg, batch_size=batch_size,
                                              seq_len=seq_len, seed=s)
               for s in range(4)]
    sleep_s = sleep_ms / 1e3

    def slow_batches(i):
        time.sleep(sleep_s)      # the injected loader stall
        return uniques[i % len(uniques)]

    ad = AutoDist(strategy_builder=AllReduce())
    runner = ad.create_distributed_session(loss_fn, params, optax.adam(1e-3),
                                           example_batch=uniques[0])

    was_enabled = telemetry.enabled()
    profiling.enable()    # attribution on: the gate reads data_wait shares

    def leg(depth_):
        """One timed train() run from the same start params; returns
        (steps/s, period-weighted data_wait share, producer_wait delta,
        final params)."""
        profiling.reset()
        wait0 = telemetry.counter("data.producer_wait").value
        t0 = time.perf_counter()
        final = training.train(runner, params, slow_batches, steps,
                               log_every=log_every, prefetch_depth=depth_)
        dt = time.perf_counter() - t0
        periods = profiling.attribution_periods()
        total_s = sum(p["period_s"] for p in periods)
        share = (sum(p["shares"]["data_wait"] * p["period_s"]
                     for p in periods) / total_s) if total_s else None
        wait_s = telemetry.counter("data.producer_wait").value - wait0
        return steps / dt, share, wait_s, jax.device_get(
            runner.logical_params(final))

    leg(0)   # compile + warmup (both loops share the compiled step)
    best = {"sync": 0.0, "prefetched": 0.0}
    sync_share = pf_share = None
    producer_wait_s = 0.0
    params_sync = params_pf = None
    for _ in range(rounds):   # interleaved: load noise hits both sides
        rate, share, _, params_sync = leg(0)
        if rate > best["sync"]:
            best["sync"], sync_share = rate, share
        rate, share, wait_s, params_pf = leg(depth)
        if rate > best["prefetched"]:
            best["prefetched"], pf_share = rate, share
            producer_wait_s = wait_s
    profiling.reset()
    profiling.disable()
    telemetry.clear()
    if was_enabled:
        telemetry.enable()
    else:
        telemetry.disable()

    flat_a = jax.tree_util.tree_leaves(params_sync)
    flat_b = jax.tree_util.tree_leaves(params_pf)
    bit_identical = len(flat_a) == len(flat_b) and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(flat_a, flat_b))
    band = next(r["band"] for r in alerts.DEFAULT_RULES
                if r["name"] == "data_wait_drift")
    ratio = best["prefetched"] / best["sync"] if best["sync"] else 0.0
    injected_s = steps * sleep_s

    result = {
        "metric": f"data_plane ({platform} x{n_dev}, d{cfg.d_model}"
                  f"x{cfg.n_layers}, seq{seq_len}, bs{batch_size}, "
                  f"loader sleep {sleep_ms:g}ms, depth {depth})",
        "unit": "steps/s",
        "rows": {"sync": round(best["sync"], 2),
                 "prefetched": round(best["prefetched"], 2)},
        "prefetch_vs_sync": round(ratio, 4),
        "data_wait_share": {"sync": round(sync_share, 4)
                            if sync_share is not None else None,
                            "prefetched": round(pf_share, 4)
                            if pf_share is not None else None},
        "drift_band": band,
        "producer_wait_s": round(producer_wait_s, 3),
        "injected_loader_s": round(injected_s, 3),
        "bit_identical": bit_identical,
    }
    if not bit_identical:
        print("WARNING: prefetched final params are NOT bit-identical to "
              "the synchronous path's — the producer reordered or altered "
              "batches (see data/prefetch.py ordering contract)",
              file=sys.stderr)
    if pf_share is not None and pf_share >= band:
        print(f"WARNING: prefetched data_wait share {pf_share:.3f} is not "
              f"below the data_wait_drift band ({band}) — the shipped "
              f"alert would still page under prefetch", file=sys.stderr)
    if producer_wait_s < 0.5 * injected_s:
        print(f"WARNING: data.producer_wait booked {producer_wait_s:.2f}s "
              f"of the {injected_s:.2f}s injected loader stall — the slow "
              f"loader is no longer visible in producer telemetry",
              file=sys.stderr)
    try:
        with open(_baseline_path()) as f:
            recorded = json.load(f).get("data_plane")
        if recorded and recorded.get("platform") == platform:
            floor = recorded.get("min_ratio", 1.2)
            if ratio < floor:
                print(f"WARNING: prefetched path is {ratio:.2f}x the sync "
                      f"steps/s under the injected slow loader, below the "
                      f"{floor:.2f}x floor — the producer stopped hiding "
                      f"the stall (see PERF_BASELINE.json data_plane)",
                      file=sys.stderr)
    except (OSError, KeyError, ValueError, TypeError, ZeroDivisionError):
        pass  # a missing/mangled snapshot must not break the bench
    print(json.dumps(result))
    return result


def selfheal_bench(steps_per_worker: int = 60, crash_at: int = 25,
                   dim: int = 256):
    """Self-healing runtime gate: kill one async-PS worker mid-run with the
    REAL fault harness (``testing/faults.py`` — abrupt socket teardown, the
    server sees exactly what a killed process produces), let the recovery
    plane evict it and the supervising harness respawn a replacement that
    re-registers and catches up on the chief's LIVE params over the
    ``read_min`` path, and measure what the incident cost. Gated numbers in
    the PERF_BASELINE.json ``selfheal`` row:

    - the faulted run COMPLETES (every planned step applied) with FINITE
      final params — the acceptance property itself;
    - ``post_vs_free``: steps/s from the crash moment to the end of the
      faulted run must be >= ``min_ratio`` (0.6) of the fault-free run's
      steps/s — eviction + rejoin + catch-up must cost a blip, not the run;
    - the recovery plane actually acted: >= 1 eviction and >= 1 rejoin
      booked (driving real failures is the point — a silent pass with no
      membership action means the fault never fired)."""
    import sys
    import threading

    import jax
    import jax.numpy as jnp
    import optax

    from autodist_tpu import AutoDist
    from autodist_tpu.parallel import recovery
    from autodist_tpu.parallel.ps_transport import PSServer, RemotePSWorker
    from autodist_tpu.strategy import PS
    from autodist_tpu.testing import faults

    platform = jax.devices()[0].platform
    rng = np.random.RandomState(0)
    w_true = rng.randn(dim, 1).astype(np.float32)

    def batch_for(seed):
        r = np.random.RandomState(seed)
        x = r.randn(64, dim).astype(np.float32)
        return {"x": x, "y": x @ w_true + 0.01 * r.randn(64, 1)
                .astype(np.float32)}

    def loss_fn(p, b):
        return jnp.mean((b["y"] - b["x"] @ p["w"]) ** 2)

    def params_init():
        return {"w": np.zeros((dim, 1), np.float32)}

    n_workers = 2

    def run_leg(crash):
        """One full run: ``n_workers`` remote workers over a loopback
        PSServer, ``steps_per_worker`` steps each; with ``crash``, worker 1
        dies at its step ``crash_at`` and the harness respawns a
        replacement (the coordinator's AUTODIST_WORKER_FAILURE=respawn
        policy in miniature — in-process so the bench is subprocess-free).
        Returns (total steps/s, post-crash steps/s, final params)."""
        # Fresh recovery log per leg: the clean legs' teardown books
        # disconnect retires too, and the acted-check below must measure
        # THIS leg's fault, not accumulated teardown noise.
        recovery.reset()
        ad = AutoDist(strategy_builder=PS(staleness=4))
        runner = ad.create_distributed_session(
            loss_fn, params_init(), optax.sgd(0.05),
            example_batch=batch_for(0), num_workers=n_workers)
        runner.init(params_init())
        server = PSServer(runner, host="127.0.0.1", watchdog=False)
        addr = "%s:%d" % server.address
        if crash:
            faults.install(f"worker_crash@step={crash_at},worker=1")
        crash_t = {}

        def drive(worker_id):
            worker = RemotePSWorker(addr, runner, worker_id=worker_id)
            i = 0
            try:
                while i < steps_per_worker:
                    try:
                        worker.step(batch_for(worker_id * 10_000 + i),
                                    timeout=120)
                        i += 1
                    except faults.WorkerCrashed:
                        crash_t["t"] = time.perf_counter()
                        crash_t["applies"] = runner.service.updates_applied
                        deadline = time.time() + 30
                        while worker_id not in runner.controller._retired \
                                and time.time() < deadline:
                            time.sleep(0.005)
                        # Bounded backoff, then the replacement registers
                        # and catches up over read_min (the
                        # RemotePSWorker.rejoin path runs inside
                        # register+first pull).
                        time.sleep(recovery.backoff_s(0, 0.05, cap_s=0.2))
                        worker = RemotePSWorker(addr, runner,
                                                worker_id=worker_id)
            finally:
                worker.close()

        try:
            t0 = time.perf_counter()
            threads = [threading.Thread(target=drive, args=(wid,),
                                        name=f"bench-selfheal-{wid}")
                       for wid in range(n_workers)]
            try:
                for t in threads:
                    t.start()
            finally:
                # join in a finally: a start() failure or interrupt must
                # not leak the already-running non-daemon drive threads
                for t in threads:
                    if t.is_alive():
                        t.join()
            dt = time.perf_counter() - t0
            total = runner.service.updates_applied
            post_rate = None
            if crash and "t" in crash_t:
                post_rate = (total - crash_t["applies"]) \
                    / max(1e-9, time.perf_counter() - crash_t["t"])
            final = jax.device_get(runner.service.state.params)
            # Leg-scoped recovery counts. NOTE: "evicted" includes the
            # drive threads' clean-close disconnect retires, not just the
            # crash — the REJOIN count is the fault-specific signal (only a
            # retired slot's re-registration books one, and nothing in a
            # clean leg retires before re-registering).
            counts = recovery.recovery_snapshot()["counts"]
            return total / dt, post_rate, total, final, counts
        finally:
            faults.clear()
            server.close()
            runner.close()

    run_leg(False)   # warmup: absorbs first-process costs (native build,
    #                  transport setup) so the two timed legs pay equally
    free_rate, _, free_total, _, _ = run_leg(False)
    fault_rate, post_rate, fault_total, final, rec = run_leg(True)

    finite = all(np.isfinite(np.asarray(l)).all()
                 for l in jax.tree_util.tree_leaves(final))
    completed = fault_total == n_workers * steps_per_worker
    ratio = (post_rate or 0.0) / free_rate if free_rate else 0.0

    result = {
        "metric": f"selfheal ({platform}, {n_workers} workers x "
                  f"{steps_per_worker} steps, dim {dim}, worker 1 killed "
                  f"at step {crash_at})",
        "unit": "steps/s",
        "rows": {"fault_free": round(free_rate, 2),
                 "faulted_total": round(fault_rate, 2),
                 "post_eviction": round(post_rate or 0.0, 2)},
        "post_vs_free": round(ratio, 4),
        "completed": completed,
        "finite_params": finite,
        "evicted": rec["evicted"],
        "rejoined": rec["rejoined"],
    }
    if not completed:
        print(f"WARNING: faulted run applied {fault_total} of "
              f"{n_workers * steps_per_worker} planned steps — the "
              f"replacement did not finish the crashed worker's share",
              file=sys.stderr)
    if not finite:
        print("WARNING: faulted run's final params are not finite — the "
              "catch-up pull adopted corrupt state", file=sys.stderr)
    if rec["rejoined"] < 1:
        # The rejoin is the discriminating check: clean teardown books
        # disconnect evictions too, but only the crashed slot's replacement
        # re-registers a RETIRED slot.
        print("WARNING: recovery plane booked no rejoin — the injected "
              "crash never exercised the self-heal path", file=sys.stderr)
    try:
        with open(_baseline_path()) as f:
            recorded = json.load(f).get("selfheal")
        if recorded and recorded.get("platform") == platform:
            floor = recorded.get("min_ratio", 0.6)
            if ratio < floor:
                print(f"WARNING: post-eviction throughput is {ratio:.2f}x "
                      f"the fault-free rate, below the {floor:.2f}x floor "
                      f"— eviction/rejoin/catch-up got expensive (see "
                      f"PERF_BASELINE.json selfheal)", file=sys.stderr)
    except (OSError, KeyError, ValueError, TypeError, ZeroDivisionError):
        pass  # a missing/mangled snapshot must not break the bench
    print(json.dumps(result))
    return result


def wire_compress_bench(steps: int = 30, rounds: int = 3, dim: int = 512,
                        out_dim: int = 512, bytes_per_s: float = 25e6):
    """Priced wire-compression gate: loopback async-PS training under an
    injected slow wire (the ``wire_slow`` fault point throttles every
    ``_send_payload`` to ``bytes_per_s``), exact pushes vs int8+EF
    compressed pushes, best of ``rounds`` interleaved rounds. The gated
    numbers in the PERF_BASELINE.json ``wire_compress`` row:

    - ``compressed_vs_exact``: compressed steps/s must be >=
      ``min_ratio`` (1.2) x exact — under a wire-bound run the 4x push-byte
      cut must buy real throughput, not just smaller counters;
    - ``bytes_saved`` must be > 0 and agree with the dense-minus-wire
      accounting (the same ``ps.wire.bytes_saved`` counter adtop/adfleet
      render and the cost model's ``quantize_bytes_per_s`` fit reads);
    - both legs' final params stay finite (EF keeps the compressed run a
      faithful optimizer, not a faster diverging one).

    The same trade the autotuner prices: on a fast wire the quantize
    seconds are NOT paid back (tests pin that it declines); this bench
    injects the slow-wire regime where compression must win."""
    import sys

    import jax
    import jax.numpy as jnp
    import optax

    from autodist_tpu import AutoDist
    from autodist_tpu.parallel.ps_transport import PSServer, RemotePSWorker
    from autodist_tpu.parallel.synchronization import WirePushCompressor
    from autodist_tpu.strategy import PS
    from autodist_tpu.testing import faults

    platform = jax.devices()[0].platform
    rng = np.random.RandomState(0)
    w_true = rng.randn(dim, out_dim).astype(np.float32)
    batch = {"x": rng.randn(32, dim).astype(np.float32)}
    batch["y"] = batch["x"] @ w_true

    def loss_fn(p, b):
        return jnp.mean((b["y"] - b["x"] @ p["w"]) ** 2)

    dense_bytes = dim * out_dim * 4

    def run_leg(wire_dtype):
        """One timed leg: a fresh loopback session, throttled wire, and an
        explicitly injected compressor (exact = inactive)."""
        ad = AutoDist(strategy_builder=PS(sync=False))
        runner = ad.create_distributed_session(
            loss_fn, {"w": np.zeros((dim, out_dim), np.float32)},
            optax.sgd(0.01), example_batch=batch, num_workers=1)
        runner.init({"w": np.zeros((dim, out_dim), np.float32)})
        server = PSServer(runner, host="127.0.0.1", watchdog=False)
        comp = WirePushCompressor(wire_dtype, min_bytes=1024)
        worker = RemotePSWorker("%s:%d" % server.address, runner,
                                worker_id=0, overlap=False, compressor=comp)
        try:
            worker.warmup(batch)
            faults.install(f"wire_slow@bytes_per_s={bytes_per_s}")
            t0 = time.perf_counter()
            for _ in range(steps):
                worker.step(batch, timeout=120)
            dt = time.perf_counter() - t0
            final = jax.device_get(runner.service.state.params)
            finite = all(np.isfinite(np.asarray(l)).all()
                         for l in jax.tree_util.tree_leaves(final))
            return steps / dt, comp, finite
        finally:
            faults.clear()
            worker.close()
            server.close()
            runner.close()

    run_leg("")   # warmup leg: first-process transport/compile costs
    exact_rate, int8_rate = 0.0, 0.0
    comp = None
    finite_all = True
    for _ in range(rounds):   # interleaved best-of: load noise hits both
        r, _, f1 = run_leg("")
        exact_rate = max(exact_rate, r)
        r, c, f2 = run_leg("int8")
        if r > int8_rate:
            int8_rate, comp = r, c
        finite_all = finite_all and f1 and f2

    ratio = int8_rate / exact_rate if exact_rate else 0.0
    result = {
        "metric": f"wire_compress ({platform}, loopback async-PS, "
                  f"{dim}x{out_dim} f32 grads ({dense_bytes // 1024} KiB "
                  f"dense), wire throttled to "
                  f"{bytes_per_s / 1e6:.0f} MB/s, {steps} steps, best of "
                  f"{rounds})",
        "unit": "steps/s",
        "rows": {"exact": round(exact_rate, 2),
                 "int8_ef": round(int8_rate, 2)},
        "compressed_vs_exact": round(ratio, 4),
        "bytes_saved": comp.bytes_saved,
        "bytes_saved_per_step": comp.bytes_saved // steps,
        "finite_params": finite_all,
    }
    if comp.bytes_saved <= 0 \
            or comp.bytes_saved != comp.bytes_in - comp.bytes_out:
        print("WARNING: bytes_saved accounting is inconsistent "
              f"(in {comp.bytes_in}, out {comp.bytes_out}, saved "
              f"{comp.bytes_saved}) — the compressor's counters no longer "
              "mean dense-minus-wire", file=sys.stderr)
    if not finite_all:
        print("WARNING: a leg's final params are not finite — compression "
              "corrupted the optimizer trajectory", file=sys.stderr)
    try:
        with open(_baseline_path()) as f:
            recorded = json.load(f).get("wire_compress")
        if recorded and recorded.get("platform") == platform:
            floor = recorded.get("min_ratio", 1.2)
            if ratio < floor:
                print(f"WARNING: compressed push is {ratio:.2f}x the exact "
                      f"steps/s under the injected slow wire, below the "
                      f"{floor:.2f}x floor — compression stopped paying for "
                      f"its quantize cost (see PERF_BASELINE.json "
                      f"wire_compress)", file=sys.stderr)
    except (OSError, KeyError, ValueError, TypeError, ZeroDivisionError):
        pass  # a missing/mangled snapshot must not break the bench
    print(json.dumps(result))
    return result


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--unroll", type=str, default="",
        help="comma-separated unroll factors (e.g. 1,2,4,8): measure the "
             "fused multi-step path (runner.run_many) at each factor and "
             "print an unroll-curve JSON line instead of the flagship "
             "measurement; on CPU a tiny host-bound model isolates the "
             "dispatch overhead the fusion amortizes")
    parser.add_argument(
        "--wire", action="store_true",
        help="measure the PS transport's zero-copy wire path (encode_parts/"
             "sendmsg/recycled-buffer alias decode) against the legacy "
             "copying codec on a >=32 MiB dense pytree round-trip, and diff "
             "the speedup against the recorded ps_wire row in "
             "PERF_BASELINE.json; CPU-only host work, runs anywhere")
    parser.add_argument(
        "--telemetry-overhead", action="store_true",
        help="measure the host-telemetry cost on the CPU micro-model: "
             "steps/s with telemetry disabled vs enabled plus the disabled "
             "no-op span cost in ns, gated against the telemetry_overhead "
             "row in PERF_BASELINE.json (disabled mode must stay within "
             "max_disabled_overhead_pct of step time)")
    parser.add_argument(
        "--health-overhead", action="store_true",
        help="measure the training-health monitor cost on the CPU "
             "micro-model: steps/s with the fused on-device numerics bundle "
             "disabled vs enabled (best of interleaved rounds), gated "
             "against max_overhead_pct in the PERF_BASELINE.json "
             "health_overhead row (enabled monitors must stay within 2%% "
             "of a host-bound step)")
    parser.add_argument(
        "--attr-overhead", action="store_true",
        help="measure the performance-attribution plane's cost on the CPU "
             "micro-model: steps/s with profiling disabled vs enabled plus "
             "the direct per-dispatch count and per-boundary attribution "
             "costs, gated against max_overhead_pct in the "
             "PERF_BASELINE.json attr_overhead row; writes the enabled "
             "run's profile JSON into AUTODIST_PROFILE_DIR when set (the "
             "adprof self-diff smoke reads it)")
    parser.add_argument(
        "--metrics-overhead", action="store_true",
        help="measure the fleet metrics plane's cost on the CPU micro-model: "
             "steps/s with the plane disabled vs enabled (history sampling + "
             "shipped alert rules + one OpenMetrics render per boundary) "
             "plus the direct per-boundary sample/render costs, gated "
             "against max_overhead_pct in the PERF_BASELINE.json "
             "metrics_overhead row")
    parser.add_argument(
        "--mem-overhead", action="store_true",
        help="measure the memory plane's cost on the CPU micro-model: "
             "steps/s with the census idle vs armed (params + opt_state "
             "re-tag and one attributed sample_device_memory per boundary) "
             "plus the direct per-boundary tag/sample costs, gated against "
             "max_overhead_pct in the PERF_BASELINE.json mem_overhead row")
    parser.add_argument(
        "--trace-pull-overhead", action="store_true",
        help="measure the cluster trace plane's pull cost: fill the span "
             "ring to capacity, report the chief-side snapshot+encode stall "
             "and the loopback round-trip of one `trace` opcode pull, gated "
             "against max_stall_ms in the PERF_BASELINE.json trace_pull row")
    parser.add_argument(
        "--reqtrace-overhead", action="store_true",
        help="measure the request-trace plane's cost on a real 1-replica "
             "router fleet: req/s with the lifecycle ring disarmed vs armed "
             "(AUTODIST_REQTRACE=1) plus the direct per-mark costs, with "
             "the armed mark cost x marks-per-request share of request "
             "latency gated against max_overhead_pct in the "
             "PERF_BASELINE.json reqtrace_overhead row")
    parser.add_argument(
        "--zero", action="store_true",
        help="measure ZeRO weight-update sharding (AUTODIST_ZERO / zero=1) "
             "on the CPU micro-model at simulated dp>=2: per-device "
             "optimizer-state bytes and steps/s, unsharded vs sharded, "
             "gated against min_opt_bytes_ratio in the PERF_BASELINE.json "
             "zero_update row (must run first in a fresh process so the "
             "simulated devices can be created)")
    parser.add_argument(
        "--serve", action="store_true",
        help="measure the serving plane: loopback requests/s and p99 total "
             "latency at a fixed offered load (mixed short/long generations "
             "on the tiny LM through a real InferenceServer), static vs "
             "continuous batching over one shared engine, gated against the "
             "serving row in PERF_BASELINE.json (continuous must beat static "
             "on requests/s at equal-or-better p99)")
    parser.add_argument(
        "--serve-fleet", action="store_true",
        help="measure fleet serving: paged vs dense concurrent requests at "
             "the same KV HBM budget with bit-identical outputs (gated "
             "against min_concurrency_ratio in the PERF_BASELINE.json "
             "serve_fleet row), router 2-replica vs 1-replica rps, and the "
             "kill-a-replica leg (one replica killed with requests in "
             "flight must cost ZERO client-visible failures and book >= 1 "
             "respawn)")
    parser.add_argument(
        "--data-plane", action="store_true",
        help="measure the input-data plane: train() under an injected slow "
             "host loader (fixed per-batch sleep), synchronous feed vs the "
             "async prefetch producer, gated against the data_plane row in "
             "PERF_BASELINE.json (prefetched >= min_ratio x sync steps/s, "
             "data_wait share below the data_wait_drift band, "
             "data.producer_wait still naming the loader, bit-identical "
             "params)")
    parser.add_argument(
        "--selfheal", action="store_true",
        help="measure the self-healing runtime: kill one async-PS worker "
             "mid-run with the fault harness (testing/faults.py), let the "
             "recovery plane evict it and a respawned replacement rejoin + "
             "catch up over read_min, gated against the selfheal row in "
             "PERF_BASELINE.json (run completes with finite params; "
             "post-eviction steps/s >= min_ratio x fault-free)")
    parser.add_argument(
        "--wire-compress", action="store_true",
        help="measure the priced wire-compression path: loopback async-PS "
             "training under an injected slow wire (wire_slow fault point), "
             "exact pushes vs int8+error-feedback compressed pushes, gated "
             "against the wire_compress row in PERF_BASELINE.json "
             "(compressed >= min_ratio x exact steps/s, bytes_saved "
             "accounting consistent, finite params both legs)")
    parser.add_argument(
        "--autotune", action="store_true",
        help="run the plan autotuner's full predict-prune-probe search on "
             "the CPU micro-model and gate the winner: tuned plan steps/s "
             "must be >= min_ratio x the default plan's (PERF_BASELINE.json "
             "autotune row) and stage-1 pruning must measure at most top-k "
             "of the enumerated candidates; reports the search cost")
    parser.add_argument(
        "--profile", type=int, default=0, metavar="N",
        help="dump a jax.profiler trace (Perfetto/TensorBoard format) of an "
             "N-step window after warmup; the trace directory is reported in "
             "the JSON line as profile_trace")
    args = parser.parse_args(argv)
    if args.wire:
        wire_bench()
        return
    if args.telemetry_overhead:
        telemetry_overhead()
        return
    if args.health_overhead:
        health_overhead()
        return
    if args.attr_overhead:
        attr_overhead()
        return
    if args.metrics_overhead:
        metrics_overhead()
        return
    if args.mem_overhead:
        mem_overhead()
        return
    if args.trace_pull_overhead:
        trace_pull_overhead()
        return
    if args.reqtrace_overhead:
        reqtrace_overhead()
        return
    if args.zero:
        zero_update_bench()
        return
    if args.serve:
        serve_bench()
        return
    if args.serve_fleet:
        serve_fleet_bench()
        return
    if args.data_plane:
        data_plane_bench()
        return
    if args.selfheal:
        selfheal_bench()
        return
    if args.wire_compress:
        wire_compress_bench()
        return
    if args.autotune:
        autotune_bench()
        return
    if args.unroll:
        try:
            factors = [int(f) for f in args.unroll.split(",") if f.strip()]
        except ValueError:
            factors = []
        if not factors or any(f < 1 for f in factors):
            parser.error(f"--unroll needs comma-separated positive integers, "
                         f"got {args.unroll!r}")
        unroll_sweep(factors)
        return

    import sys

    import jax

    import chip_smoke
    from autodist_tpu.telemetry import profiling
    from autodist_tpu.utils import compile_cache
    from autodist_tpu.utils import flops as flops_util

    devices = jax.devices()
    platform, n_dev = devices[0].platform, len(devices)
    peaks = profiling.peak_spec(devices[0])
    if platform != "tpu" or peaks.flops_per_s is None:
        # No CPU fallback: a rate from another machine under this metric's
        # name is worse than no rate. `chip_smoke.run(chip_smoke.TINY,
        # require_tpu=False)` is the CPU rehearsal of the same path.
        print(json.dumps({
            "ok": False,
            "error": f"the flagship benchmark needs a TPU with known peaks; "
                     f"jax.devices()[0] is platform {platform!r}, device_kind "
                     f"{devices[0].device_kind!r} (peaks: {peaks.source})"}))
        sys.exit(1)
    compile_cache.configure()

    # lm1b-class flagship config (chip_smoke.FLAGSHIP), bf16 activations,
    # Pallas fused head+loss: measured faster than the XLA head at equal
    # batch (410k vs 398k tokens/s at 256) AND it unlocks batch 384, which
    # OOMs with materialized logits. Swept on a v5e chip in round 5: fused
    # head 384/device = ~426k tokens/s vs 410k at 256 and 421k at 512; XLA
    # head topped out at ~404k (bs 256; 384 OOMs); seq512 loses (346k at 128).
    # Gradient accumulation on top (same 384-seq micro-batch, Adam applied
    # once per ACCUM micro-batches) amortizes the optimizer + dispatch:
    # 433.6k@2, 436.3k@3, 441.2k@8, plateau ~442k@16 — accum 8 (global batch
    # 3072 seqs = 786k tokens, a standard large-batch LM config) ships.
    size = chip_smoke.FLAGSHIP
    seq_len, accum = size.seq_len, size.accum
    batch_size = size.micro_batch * n_dev * accum
    cfg, _, batch, step = chip_smoke.build_flagship(size, batch_size, accum)
    # Device-resident batch: measure the chip, not the host link.
    batch = step.runner.shard_batch(batch)

    # Warmup (compile + first dispatch), then timed steps. The final host read
    # is the sync barrier: the last loss depends on the whole state chain.
    for _ in range(2):
        loss = step(batch)
    _ = float(loss)
    trace_dir = None
    if args.profile > 0:
        # Profiled window AFTER warmup (the trace sees steady-state steps,
        # not compilation) and BEFORE the timed loop (tracing overhead must
        # not contaminate the reported rate).
        from autodist_tpu.utils import tracing
        with tracing.trace("bench_flagship") as trace_dir:
            for _ in range(args.profile):
                loss = step(batch)
            _ = float(loss)  # completion fence inside the traced window
    n_steps = 20
    t0 = time.perf_counter()
    for _ in range(n_steps):
        loss = step(batch)
    _ = float(loss)
    dt = time.perf_counter() - t0

    tokens_per_step = batch_size * seq_len
    tokens_per_sec = tokens_per_step * n_steps / dt
    per_device = tokens_per_sec / n_dev

    # MFU from the analytic per-token count (the fused pallas head is invisible
    # to XLA's flop analysis, so the compiled-module count would under-report).
    flops_per_token = flops_util.transformer_flops_per_token(
        cfg.d_model, cfg.n_layers, cfg.d_ff, cfg.vocab_size, seq_len)
    mfu = flops_per_token * per_device / peaks.flops_per_s

    result = {
        "metric": f"transformer_lm_train_tokens_per_sec ({platform} x{n_dev}, "
                  f"d{cfg.d_model}x{cfg.n_layers}, seq{seq_len}, "
                  f"bs{batch_size}={batch_size // accum}x{accum}accum)",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "device": {"platform": platform, "kind": devices[0].device_kind,
                   "count": n_dev},
        "vs_baseline": round(per_device / BASELINE_TOKENS_PER_SEC_PER_DEVICE, 3),
        "flops_per_token": round(flops_per_token),
        "mfu": round(mfu, 4),
    }
    if trace_dir is not None:
        result["profile_trace"] = trace_dir

    # Attribution postscript — AFTER the timed loop, so the line can say
    # where the step's wall time goes without taxing the reported rate: a
    # short profiled window (3 steps + one observe_period). The analytic
    # per-token count stands in for XLA's where the fused pallas head hides
    # flops from cost analysis.
    from autodist_tpu import telemetry
    was_on = telemetry.enabled()
    profiling.enable()
    profiling.reset()
    profiling.set_analytic_flops(flops_per_token * tokens_per_step)
    profiling.observe_period()        # open a clean window
    for _ in range(3):
        loss = step(batch)
    _ = float(loss)
    rec = profiling.observe_period()
    result["attr"] = rec["shares"] if rec else None
    profiling.reset()
    profiling.disable()
    if not was_on:
        telemetry.disable()
    # Regression annotation vs the recorded best (PERF_BASELINE.json, round-5
    # per-chip rates): warn on stderr past the threshold. Compared per device
    # so a multi-chip aggregate can't mask a per-chip regression.
    try:
        with open(_baseline_path()) as f:
            base = json.load(f)
        best = base["rows"]["flagship"]["rate"]
        threshold = base.get("threshold_pct", 2.0)
        result["vs_best"] = round(per_device / best, 4)
        if per_device < best * (1.0 - threshold / 100.0):
            print(f"WARNING: flagship {per_device:,.0f} tokens/s/chip is "
                  f"{100 * (1 - per_device / best):.1f}% below the "
                  f"recorded best {best:,.0f} (threshold {threshold}%) — "
                  f"see PERF_BASELINE.json", file=sys.stderr)
    except (OSError, KeyError, ValueError, TypeError):
        pass  # a missing/mangled snapshot must not break the bench
    print(json.dumps(result))


if __name__ == "__main__":
    main()
