"""Unified runtime telemetry: spans, metrics registry, stats plane.

Covers the three telemetry planes (docs/usage/observability.md): host span
recording and its Chrome trace-event export schema, the Counter/Gauge/
Histogram registry's deterministic wire-encodable snapshot, the disabled-mode
no-op contract (one attribute read per span), and a ``stats``-opcode
round-trip over a real loopback PS pair. Plus the satellite pins: the
ThroughputMeter's frozen run clock, narrow ``_sync`` failure handling,
collision-free trace dirs, and ``_RecvBuffer`` recycle accounting.

Pure in-process host tests — no subprocess spawns (GL008-clean), named to
sort inside the tier-1 window.
"""

import json
import threading
import time

import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.telemetry import metrics as tmetrics
from autodist_tpu.telemetry import spans as tspans


@pytest.fixture(autouse=True)
def _telemetry_reset():
    """Leave process-global telemetry exactly as found: disabled, empty ring
    (the registry is additive-only and harmless to share)."""
    telemetry.disable()
    telemetry.clear()
    yield
    telemetry.disable()
    telemetry.clear()


# ------------------------------------------------------------------- spans

def test_span_records_and_nests():
    telemetry.enable()
    with telemetry.span("outer", kind="test"):
        with telemetry.span("inner"):
            time.sleep(0.001)
    recorded = {name: (tid, t0, dur, args)
                for name, tid, t0, dur, args in telemetry.snapshot_spans()}
    assert set(recorded) == {"outer", "inner"}
    o_tid, o_t0, o_dur, o_args = recorded["outer"]
    i_tid, i_t0, i_dur, _ = recorded["inner"]
    assert o_tid == i_tid == threading.get_ident()
    # Containment is the nesting contract (Perfetto stacks same-thread
    # complete events by time-range containment).
    assert o_t0 <= i_t0
    assert i_t0 + i_dur <= o_t0 + o_dur
    assert o_args == {"kind": "test"}


def test_span_thread_awareness():
    telemetry.enable()
    done = threading.Event()

    def worker():
        with telemetry.span("from_thread"):
            pass
        done.set()

    t = threading.Thread(target=worker, name="telemetry-test-thread")
    with telemetry.span("from_main"):
        t.start()
        t.join(timeout=10)
    assert done.wait(timeout=10)
    tids = {name: tid for name, tid, *_ in telemetry.snapshot_spans()}
    assert tids["from_main"] != tids["from_thread"]


def test_traced_decorator_records_per_call():
    @telemetry.traced("deco_span")
    def f(x):
        return x + 1

    assert f(1) == 2                       # disabled at call: no record
    assert telemetry.snapshot_spans() == []
    telemetry.enable()                      # decorated BEFORE enabling
    assert f(2) == 3
    assert [s[0] for s in telemetry.snapshot_spans()] == ["deco_span"]


def test_span_ring_is_bounded():
    telemetry.enable()
    cap = tspans._STATE.ring_t0.maxlen
    assert cap is not None and cap >= 1
    for i in range(min(cap, 1000) + 50):
        with telemetry.span("s"):
            pass
    assert tspans._STATE.ring_len() <= cap
    # The five ring columns evict in lockstep — they can never misalign.
    st = tspans._STATE
    assert len(st.ring_name) == len(st.ring_tid) == len(st.ring_t0) \
        == len(st.ring_dur) == len(st.ring_args)


def test_chrome_trace_export_schema(tmp_path):
    telemetry.enable()
    with telemetry.span("a", step=3):
        with telemetry.span("b"):
            pass
    path = str(tmp_path / "host_spans.json")
    assert telemetry.export_chrome_trace(path) == path
    doc = json.load(open(path))
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    for ev in events:
        assert ev["ph"] in ("X", "M")
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert isinstance(ev["name"], str)
        if ev["ph"] == "X":
            assert isinstance(ev["ts"], float) and ev["ts"] >= 0.0
            assert isinstance(ev["dur"], float) and ev["dur"] >= 0.0
            assert isinstance(ev["args"], dict)
        else:
            assert ev["name"] == "thread_name"
    names = [ev["name"] for ev in events if ev["ph"] == "X"]
    assert sorted(names) == ["a", "b"]
    arg_ev = next(ev for ev in events if ev["name"] == "a")
    assert arg_ev["args"] == {"step": 3}
    # pid/clock_offset_ns parameters (cluster trace plane): same schema, the
    # lane relabeled and every ts uniformly shifted — defaults unchanged.
    shifted = telemetry.chrome_trace_events(pid=9, clock_offset_ns=1_000)
    assert all(ev["pid"] == 9 for ev in shifted)
    for ev, base_ev in zip((e for e in shifted if e["ph"] == "X"),
                           (e for e in events if e["ph"] == "X")):
        assert ev["ts"] - base_ev["ts"] == pytest.approx(1.0)  # 1000ns = 1µs


def test_disabled_span_is_single_attribute_check():
    """The disabled fast path's cost contract: exactly ONE attribute read per
    ``span()`` call, returning the shared no-op context manager, with no ring
    growth. A second attribute touch here is a hot-path regression (gated at
    runtime by bench.py --telemetry-overhead)."""

    class _CountingState:
        def __init__(self):
            self.reads = 0

        @property
        def enabled(self):
            self.reads += 1
            return False

    counting = _CountingState()
    real = tspans._STATE
    tspans._STATE = counting
    try:
        cms = {telemetry.span("x"), telemetry.span("y", k=1)}
        for _ in range(48):
            with telemetry.span("z"):
                pass
        reads = counting.reads
    finally:
        tspans._STATE = real
    assert len(cms) == 1                       # the one shared null span
    assert reads == 50                         # one read per span() call
    assert telemetry.snapshot_spans() == []    # nothing recorded


# ----------------------------------------------------------------- registry

def test_registry_snapshot_deterministic():
    r1, r2 = tmetrics.Registry(), tmetrics.Registry()
    for reg, order in ((r1, ("b.z", "a.x", "m.c")),
                      (r2, ("m.c", "b.z", "a.x"))):
        for name in order:
            reg.counter(name)
        reg.counter("b.z").inc(2)
        reg.counter("a.x").inc(1)
        reg.counter("m.c").inc(3)
        reg.gauge("g.depth").set(1)
        reg.histogram("h.lag", buckets=(1, 2)).observe(1.5)
    assert r1.snapshot() == r2.snapshot()      # registration order irrelevant
    assert list(r1.snapshot()) == sorted(r1.snapshot())
    assert r1.snapshot()["b.z"] == 2
    # snapshot values are wire-encodable as-is (the stats opcode's contract)
    from autodist_tpu.parallel import wire
    assert wire.decode(wire.encode(r1.snapshot())) == r1.snapshot()


def test_histogram_family_bucket_overrides():
    """Per-family default-bucket resolution (PR 7): names under a
    BUCKET_FAMILIES prefix get that family's edges (serve latencies resolve
    at ms scale), longest prefix wins, explicit buckets always override, and
    names outside every family keep the pre-existing SECONDS_BUCKETS default
    — the snapshot schema of old histograms is unchanged."""
    assert tmetrics.family_buckets("serve.latency_s") == tmetrics.MS_BUCKETS
    assert tmetrics.family_buckets("serve.latency_s.total") \
        == tmetrics.MS_BUCKETS
    # Prefix match is component-wise: a sibling name is NOT in the family.
    assert tmetrics.family_buckets("serve.latency_sx") \
        == tmetrics.SECONDS_BUCKETS
    assert tmetrics.family_buckets("train.step_s") == tmetrics.SECONDS_BUCKETS

    reg = tmetrics.Registry()
    ms = reg.histogram("serve.latency_s.queue")
    assert ms.buckets == tmetrics.MS_BUCKETS
    old = reg.histogram("train.step_s")
    assert old.buckets == tmetrics.SECONDS_BUCKETS
    explicit = reg.histogram("serve.latency_s.custom", buckets=(1, 2))
    assert explicit.buckets == (1, 2)
    # Snapshot schema: the family's edges appear as le: keys, same shape as
    # every other histogram.
    ms.observe(0.003)
    snap = reg.snapshot()["serve.latency_s.queue"]
    assert set(snap) == {f"le:{b:g}" for b in tmetrics.MS_BUCKETS} \
        | {"le:+inf", "count", "sum"}
    assert snap["le:0.005"] == 1


def test_registry_get_or_create_and_type_guard():
    reg = tmetrics.Registry()
    assert reg.counter("c") is reg.counter("c")
    with pytest.raises(TypeError):
        reg.gauge("c")


def test_histogram_bucket_edges():
    h = tmetrics.Histogram("h", buckets=(1, 2, 4))
    for v in (0.5, 1, 1.5, 2, 4.5):
        h.observe(v)
    snap = h.snapshot()
    # le semantics: a value equal to a bound lands IN that bound's bucket.
    assert snap["le:1"] == 2      # 0.5, 1
    assert snap["le:2"] == 2      # 1.5, 2
    assert snap["le:4"] == 0
    assert snap["le:+inf"] == 1   # 4.5
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(9.5)
    assert h.format_compact() == "{1:2,2:2,+inf:1}"
    with pytest.raises(ValueError):
        tmetrics.Histogram("bad", buckets=(2, 1))


def test_emit_metrics_rides_benchmark_logger():
    from autodist_tpu.utils.benchmark_logger import BaseBenchmarkLogger

    class _Capture(BaseBenchmarkLogger):
        def __init__(self):
            self.rows = []

        def log_metric(self, name, value, unit=None, global_step=None,
                       extras=None):
            self.rows.append((name, value, global_step, extras))

    reg = telemetry.registry()
    reg.counter("emit.test_counter").inc(7)
    reg.histogram("emit.test_hist", buckets=(1,)).observe(0.5)
    sink = _Capture()
    n = telemetry.emit_metrics(global_step=42, logger=sink)
    assert n == len(sink.rows) >= 2
    rows = {name: (value, step, extras) for name, value, step, extras
            in sink.rows}
    assert rows["emit.test_counter"][0] == 7
    assert rows["emit.test_counter"][1] == 42
    value, _, extras = rows["emit.test_hist"]
    assert value == 1 and extras["le:1"] == 1  # count + bucket dict in extras


# -------------------------------------------------- wire counters / satellites

def test_wire_counters_format_line_pinned():
    from autodist_tpu.utils.metrics import WireCounters
    wc = WireCounters()
    wc.add_sent(12_300_000, encode_s=0.0012)
    wc.add_received(67_800_000, decode_s=0.0034)
    assert wc.format_line() == ("wire tx 12.3MB/1 rx 67.8MB/1 "
                                "enc 1.20ms/msg dec 3.40ms/msg")
    assert wc.snapshot() == {"bytes_sent": 12_300_000,
                             "bytes_received": 67_800_000,
                             "msgs_sent": 1, "msgs_received": 1,
                             "encode_s": 0.0012, "decode_s": 0.0034}


def test_wire_counters_mirror_into_registry():
    from autodist_tpu.utils.metrics import WireCounters
    telemetry.enable()
    before = telemetry.registry().counter("ps.wire.bytes_sent").value
    WireCounters().add_sent(1000)
    WireCounters(mirror=False).add_sent(5000)   # per-worker views: no mirror
    after = telemetry.registry().counter("ps.wire.bytes_sent").value
    assert after - before == 1000


def test_throughput_meter_finish_freezes_average():
    from autodist_tpu.utils.metrics import ThroughputMeter
    meter = ThroughputMeter(batch_size=10, log_every=2, warmup_steps=1,
                            log=False)
    for _ in range(5):
        meter.step()
        time.sleep(0.005)
    frozen = meter.finish()
    assert frozen == meter.average is not None
    time.sleep(0.08)
    # Post-run wall time (eval/teardown) no longer dilutes the rate.
    assert meter.average == frozen
    meter.step()              # training again: the clock unfreezes
    time.sleep(0.08)
    assert meter.average != frozen


def test_step_many_of_one_is_step(monkeypatch):
    """``train()`` meters a single step as ``step_many(1, ...)``: over
    3 x log_every + 2 steps it must close the same periods and read the
    device back at the same steps as ``step()``: the warm-up dispatch and
    steps 1 + k x log_every, nowhere else."""
    from autodist_tpu.utils import metrics as umetrics
    log_every = 4

    def walk(tick):
        meter = umetrics.ThroughputMeter(batch_size=8, log_every=log_every,
                                         log=False)
        synced, closed = [], []
        monkeypatch.setattr(
            umetrics, "_sync", lambda value: synced.append(value) or 0.0)
        for i in range(1, 3 * log_every + 3):
            if tick(meter, i) is not None:
                closed.append(i)
        return synced, closed, len(meter.history), meter._run_steps

    by_step = walk(lambda m, i: m.step(sync=i))
    by_many = walk(lambda m, i: m.step_many(1, sync=i))
    assert by_many == by_step
    boundaries = [1 + k * log_every for k in (1, 2, 3)]
    assert by_step == ([1] + boundaries, boundaries, 3, 3 * log_every + 1)


def test_sync_failure_is_narrow_and_silent():
    import jax

    from autodist_tpu.utils import metrics as umetrics
    real = jax.device_get
    jax.device_get = lambda v: (_ for _ in ()).throw(RuntimeError("boom"))
    try:
        elapsed = umetrics._sync(np.ones((2,)))   # must not raise
    finally:
        jax.device_get = real
    assert isinstance(elapsed, float) and elapsed >= 0.0
    assert umetrics._sync(None) == 0.0


def test_trace_dirs_never_collide():
    from autodist_tpu import const
    from autodist_tpu.utils import tracing
    dirs = {tracing._unique_trace_dir("t") for _ in range(8)}
    assert len(dirs) == 8          # same wall-clock second, distinct dirs
    assert all(d.startswith(const.DEFAULT_TRACE_DIR) for d in dirs)


def test_recv_buffer_counts_recycles_and_fresh():
    from autodist_tpu.parallel.ps_transport import _RecvBuffer
    buf = _RecvBuffer()
    view = buf.take(128)
    assert (buf.fresh_allocs, buf.recycles) == (1, 0)
    del view                       # consume-then-drop: next take recycles
    buf.take(128)
    assert (buf.fresh_allocs, buf.recycles) == (1, 1)
    holder = buf.take(128)         # held alias: next take must go fresh
    assert buf.recycles == 2
    buf.take(128)
    assert (buf.fresh_allocs, buf.recycles) == (2, 2)
    del holder


# -------------------------------------------------------------- stats plane

class _StubPSRunner:
    """The minimal surface PSServer._dispatch drives, over a numpy-only
    ParameterService — a real gate and service without model compilation."""

    def __init__(self, staleness=2):
        from autodist_tpu.parallel.staleness import (ParameterService,
                                                     StalenessController)
        from autodist_tpu.runner import TrainState
        state = TrainState(step=np.zeros((), np.int32),
                           params={"w": np.ones((64,), np.float32)},
                           opt_state=(), ef_state=())
        self.service = ParameterService(state, lambda s, grads: s)
        self.controller = StalenessController(1, staleness=staleness)

    def add_worker(self, worker_id=None, with_generation=False):
        wid, gen = self.controller.register_with_generation(worker_id)
        handle = type("H", (), {"worker_id": wid})()
        return (handle, gen) if with_generation else handle


def test_stats_opcode_roundtrip_over_loopback():
    from autodist_tpu.parallel.ps_transport import PSServer, RemotePSWorker

    telemetry.enable()
    server = PSServer(_StubPSRunner(), host="127.0.0.1")
    host, port = server.address
    remote = RemotePSWorker(f"{host}:{port}", runner=None, worker_id=0,
                            overlap=False)
    try:
        # Drive the gate + a parameter read so there is per-worker traffic.
        remote._client.call("start_step", 0, 5.0)
        params, _, version = remote._client.call("read")
        remote._client.call("finish_step", 0)
        np.testing.assert_allclose(params["w"], 1.0)

        snap = remote.stats()
        assert set(snap) >= {"registry", "wire", "per_worker"}
        # Aggregate wire counters cover every exchange so far.
        assert snap["wire"]["msgs_received"] >= 4
        assert snap["wire"]["bytes_received"] > 0
        # Per-worker breakdown: this worker's traffic + its staleness
        # distribution from the gate (one entry, zero lag).
        w0 = snap["per_worker"][0]
        assert w0["wire"]["msgs_received"] >= 2
        assert w0["staleness"]["count"] == 1
        assert w0["staleness"]["le:0"] == 1
        # The registry snapshot mirrors the wire counters (telemetry is on).
        assert snap["registry"]["ps.wire.bytes_received"] > 0
        # The reply crossed the typed wire, so it is JSON-able plain data.
        json.dumps(snap)
    finally:
        remote.close()
        server.close()


def test_unknown_op_still_errors():
    """The stats arm must not loosen the dispatch's unknown-op handling."""
    from autodist_tpu.parallel.ps_transport import PSClientError, PSServer, \
        RemotePSWorker

    server = PSServer(_StubPSRunner(), host="127.0.0.1")
    host, port = server.address
    remote = RemotePSWorker(f"{host}:{port}", runner=None, worker_id=0,
                            overlap=False)
    try:
        with pytest.raises(PSClientError, match="unknown op"):
            remote._client.call("no_such_op")
    finally:
        remote.close()
        server.close()
