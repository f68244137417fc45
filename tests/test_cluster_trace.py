"""Cluster trace plane: trace/ping/push_trace wire ops, clock-offset rebase,
straggler watchdog, compile/memory telemetry, offline tracedump merge.

Covers the trace plane end to end (docs/usage/observability.md "Cluster
timeline"): a loopback trace-pull/push round-trip over a numpy-only stub
runner, NTP-offset math and the deterministic known-skew rebase (merged
ordering flips when the offsets say so), the PSServer watchdog flagging a
stalled and a straggling stub worker, `tools/tracedump.py` merging two JSONL
ring dumps, and the satellite pins: `export_chrome_trace(pid=,
clock_offset_ns=)`, `stats_snapshot()` uptime/last-seen, and host spans in
the profiler's own trace (`tracing.trace(with_host_spans=True)`).

Pure in-process host tests — no subprocess spawns (GL008-clean), named to
sort inside the tier-1 window (before test_image_data).
"""

import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.telemetry import cluster as tcluster


@pytest.fixture(autouse=True)
def _telemetry_reset():
    """Leave process-global telemetry as found: disabled, empty ring (the
    registry is additive-only and harmless to share)."""
    telemetry.disable()
    telemetry.clear()
    yield
    telemetry.disable()
    telemetry.clear()


def _synthetic_state(worker_id, wall_ns, offset_ns, t0s=(0,), durs=None,
                     name="step"):
    """A hand-built trace blob with a controlled clock — the deterministic-
    skew fixture (real rings in one process share one clock, so skew must be
    fabricated)."""
    n = len(t0s)
    return {
        "v": tcluster.TRACE_STATE_VERSION,
        "pid": 4242, "host": "testhost", "worker_id": worker_id,
        "wall_ns": wall_ns, "perf_ns": 0, "epoch_ns": 0,
        "clock_offset_ns": offset_ns,
        "names": [name], "name_idx": np.zeros(n, np.int32),
        "tids": [11], "tid_idx": np.zeros(n, np.int32),
        "t0_ns": np.asarray(t0s, np.int64),
        "dur_ns": np.asarray(durs if durs is not None else [10] * n, np.int64),
        "args_json": "", "thread_names": {11: "main"},
    }


# --------------------------------------------------------------- blob + rebase

def test_local_trace_state_columnar_and_wire_encodable():
    from autodist_tpu.parallel import wire

    telemetry.enable()
    for i in range(16):
        with telemetry.span("fill", idx=i & 3, obj=object()):
            pass
    with telemetry.span("other"):
        pass
    st = telemetry.local_trace_state(worker_id=5, clock_offset_ns=-7)
    assert sorted(st["names"]) == ["fill", "other"]
    assert len(st["name_idx"]) == len(st["t0_ns"]) == len(st["dur_ns"]) == 17
    assert st["worker_id"] == 5 and st["clock_offset_ns"] == -7
    assert st["name_idx"].dtype == np.int32 and st["t0_ns"].dtype == np.int64
    # Span args ride as ONE JSON string (non-encodable values stringified),
    # so the blob crosses the typed wire verbatim without per-span dict
    # encoding — the `trace`/`push_trace` payload + stall-gate contract.
    args0 = tcluster._parse_args_json(st)[0]
    assert args0["idx"] == 0 and isinstance(args0["obj"], str)
    dec = wire.decode(wire.encode(("ok", st)))[1]
    assert dec["names"] == st["names"]
    np.testing.assert_array_equal(dec["t0_ns"], st["t0_ns"])
    # wall/perf pair sampled together: a span's wall-clock start derived from
    # it lands within the snapshot's own lifetime.
    assert abs(st["wall_ns"] - time.time_ns()) < 60e9


def test_ntp_offset_median_and_uncertainty():
    # Midpoint offsets: 160-110=50, 155-105=50, 170-120=50 → all agree;
    # uncertainty = best RTT / 2 = 20 / 2.
    assert tcluster.ntp_offset([(100, 160, 120), (90, 155, 120),
                                (100, 170, 140)]) == (50, 10)
    # One wildly delayed exchange must not move the median.
    off, err = tcluster.ntp_offset(
        [(0, 50, 20), (0, 50, 20), (0, 9_000_000, 8_000_000)])
    assert off == 40 and err == 10
    with pytest.raises(ValueError):
        tcluster.ntp_offset([])


def test_known_skew_rebase_flips_merged_ordering(tmp_path):
    """The deterministic skew pin: worker B's raw wall clock is 1s AHEAD of
    worker A's, but the estimated offsets say B's clock runs 1.8s fast —
    after rebasing, B's span must come FIRST in the merged timeline."""
    a = _synthetic_state(0, wall_ns=1_000_000_000, offset_ns=500_000_000)
    b = _synthetic_state(1, wall_ns=2_000_000_000, offset_ns=-800_000_000)
    path = str(tmp_path / "merged.json")
    assert tcluster.merge_trace_states([a, b], path) == path
    doc = json.load(open(path))
    xs = {ev["pid"]: ev["ts"] for ev in doc["traceEvents"] if ev["ph"] == "X"}
    # pid lanes: worker 0 -> 1, worker 1 -> 2. Rebased starts: A = 1.5s,
    # B = 1.2s → B at origin (ts 0), A 300ms later.
    assert set(xs) == {1, 2}
    assert xs[2] == 0.0
    assert xs[1] == pytest.approx(300_000.0)  # µs
    labels = {ev["pid"]: ev["args"]["name"] for ev in doc["traceEvents"]
              if ev["ph"] == "M" and ev["name"] == "process_name"}
    assert "worker 0" in labels[1] and "worker 1" in labels[2]


def test_merge_rejects_unknown_blob_version(tmp_path):
    bad = _synthetic_state(0, 0, 0)
    bad["v"] = tcluster.TRACE_STATE_VERSION + 1
    with pytest.raises(ValueError, match="version"):
        tcluster.merge_trace_states([bad], str(tmp_path / "x.json"))


# ---------------------------------------------------------- loopback transport

class _StubPSRunner:
    """The minimal surface PSServer._dispatch drives, over a numpy-only
    ParameterService — a real gate and service without model compilation."""

    def __init__(self, num_workers=1, staleness=2):
        from autodist_tpu.parallel.staleness import (ParameterService,
                                                     StalenessController)
        from autodist_tpu.runner import TrainState
        state = TrainState(step=np.zeros((), np.int32),
                           params={"w": np.ones((64,), np.float32)},
                           opt_state=(), ef_state=())
        self.service = ParameterService(state, lambda s, grads: s)
        self.controller = StalenessController(num_workers,
                                              staleness=staleness)

    def add_worker(self, worker_id=None, with_generation=False):
        wid, gen = self.controller.register_with_generation(worker_id)
        handle = type("H", (), {"worker_id": wid})()
        return (handle, gen) if with_generation else handle


def _loopback(num_workers=1, staleness=2, **server_kw):
    from autodist_tpu.parallel.ps_transport import PSServer
    server = PSServer(_StubPSRunner(num_workers, staleness),
                      host="127.0.0.1", **server_kw)
    return server, "%s:%d" % server.address


def test_trace_pull_and_push_roundtrip_over_loopback(tmp_path):
    from autodist_tpu.parallel.ps_transport import RemotePSWorker

    telemetry.enable()
    server, addr = _loopback(watchdog=False)
    remote = RemotePSWorker(addr, runner=None, worker_id=0, overlap=False)
    try:
        offset, err = remote.estimate_clock_offset()
        # Loopback to the same process: the true offset is 0 and the NTP
        # midpoint error is RTT-bounded — far under 50ms even on a loaded box.
        assert abs(offset) < 50_000_000
        assert err >= 0
        assert remote.clock_offset_ns == offset

        with telemetry.span("pull.me", tag=1):
            pass
        blob = remote.trace()
        assert "pull.me" in blob["names"]          # the chief's ring, pulled
        assert blob["worker_id"] is None

        pushed = remote.push_trace()
        assert pushed >= 1
        deposited = server.worker_traces()
        assert set(deposited) == {0}
        assert deposited[0]["worker_id"] == 0
        assert deposited[0]["clock_offset_ns"] == offset

        path = str(tmp_path / "cluster.json")
        assert telemetry.collect_cluster_trace(path, server=server) == path
        doc = json.load(open(path))
        pids = {ev["pid"] for ev in doc["traceEvents"]}
        assert {0, 1} <= pids                      # chief lane + worker lane
        assert any(ev["ph"] == "X" for ev in doc["traceEvents"])
    finally:
        remote.close()
        server.close()


def test_stats_snapshot_gains_uptime_and_last_seen():
    from autodist_tpu.parallel.ps_transport import RemotePSWorker

    server, addr = _loopback(watchdog=False)
    remote = RemotePSWorker(addr, runner=None, worker_id=0, overlap=False)
    try:
        remote._client.call("start_step", 0, 5.0)
        remote._client.call("finish_step", 0)
        snap = remote.stats()
        assert snap["uptime_s"] >= 0.0
        assert isinstance(snap["anomalies"], list)
        assert snap["per_worker"][0]["last_seen_s"] >= 0.0
        assert snap["per_worker"][0]["last_seen_s"] <= snap["uptime_s"] + 1.0
        json.dumps(snap)                  # crossed the wire: plain data
    finally:
        remote.close()
        server.close()


def test_watchdog_flags_stalled_worker():
    from autodist_tpu.parallel.ps_transport import RemotePSWorker

    server, addr = _loopback(watchdog=True, watchdog_interval=0.05)
    remote = RemotePSWorker(addr, runner=None, worker_id=0, overlap=False)
    try:
        flags = telemetry.registry().counter("ps.straggler.flags")
        before = flags.value
        remote._client.call("start_step", 0, 5.0)
        remote._client.call("finish_step", 0)
        # Go silent: after ~3 intervals the watchdog must flag worker 0.
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and 0 not in server._watchdog.flagged:
            time.sleep(0.02)
        assert 0 in server._watchdog.flagged
        assert flags.value > before
        assert telemetry.registry().gauge(
            "ps.worker.last_seen_s.w0").value > 0.0
        kinds = {ev["name"] for ev in telemetry.events()
                 if ev.get("worker") == 0}
        assert "ps.anomaly.stall" in kinds
    finally:
        remote.close()
        server.close()


def test_watchdog_names_the_straggler():
    """Two workers, bound 1: worker 1 completes a step and parks at the
    bound; worker 0 never advances — the watchdog must name worker 0 (the
    culprit), not the parked victim."""
    server, addr = _loopback(num_workers=2, staleness=1,
                             watchdog=True, watchdog_interval=60.0)
    try:
        runner = server._runner
        runner.controller.register(0)
        runner.controller.register(1)
        server._stats_for(0)
        server._stats_for(1)
        runner.controller.finish_step(1)    # worker 1 now AT the bound
        # Deterministic direct ticks. One instant at the bound is normal
        # steady-state gating — the flag needs STALL_INTERVALS consecutive
        # ticks of persistence before it fires.
        server._watchdog._sample()
        assert server._watchdog.flagged == set()
        for _ in range(int(server._watchdog.STALL_INTERVALS) - 1):
            server._watchdog._sample()
        assert server._watchdog.flagged == {0}
        # The culprit catching up clears the condition AND the persistence
        # counter — the next bound-parked instant starts from zero again.
        runner.controller.finish_step(0)
        server._watchdog._sample()
        assert server._watchdog.flagged == set()
        assert server._watchdog._straggler_ticks == {}
        # A retired worker leaves the stall scan entirely: its frozen
        # last-seen age must not flag it forever after a clean departure.
        with server._worker_stats_lock:
            server._worker_stats[1].last_seen = time.monotonic() - 9999.0
        runner.controller.retire(1)
        server._watchdog._sample()
        assert 1 not in server._watchdog.flagged
        kinds = {ev["name"] for ev in telemetry.events()
                 if ev.get("worker") == 0}
        assert "ps.anomaly.straggler" in kinds
    finally:
        server.close()


def test_live_lags_and_bound():
    from autodist_tpu.parallel.staleness import StalenessController
    c = StalenessController(3, staleness=2)
    assert c.bound == 2
    c.finish_step(0)
    c.finish_step(0)
    c.finish_step(1)
    assert c.live_lags() == {0: 2, 1: 1, 2: 0}
    c.retire(2)
    assert c.live_lags() == {0: 1, 1: 0}


# ----------------------------------------------------------- offline tracedump

def _tracedump():
    spec = importlib.util.spec_from_file_location(
        "tracedump_cli", os.path.join(os.path.dirname(__file__), os.pardir,
                                      "tools", "tracedump.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_tracedump_merges_two_jsonl_rings(tmp_path):
    telemetry.enable()
    with telemetry.span("ring.a", n=1):
        pass
    p0 = str(tmp_path / "w0.jsonl")
    telemetry.dump_spans_jsonl(p0, worker_id=0)
    telemetry.clear()
    with telemetry.span("ring.b"):
        pass
    p1 = str(tmp_path / "w1.jsonl")
    telemetry.dump_spans_jsonl(p1, worker_id=1, clock_offset_ns=1000)

    # JSONL round-trips losslessly (incl. the offset override hook).
    st = telemetry.load_trace_jsonl(p1)
    assert st["worker_id"] == 1 and st["clock_offset_ns"] == 1000
    assert telemetry.load_trace_jsonl(p1, clock_offset_ns=5)[
        "clock_offset_ns"] == 5

    out = str(tmp_path / "merged.json")
    td = _tracedump()
    assert td.merge_dumps(out, [p0, p1], offsets={1: 2000}) == out
    doc = json.load(open(out))
    by_pid = {}
    for ev in doc["traceEvents"]:
        if ev["ph"] == "X":
            by_pid.setdefault(ev["pid"], []).append(ev["name"])
    assert set(by_pid) == {1, 2}           # one lane per worker id
    assert by_pid[1] == ["ring.a"] and by_pid[2] == ["ring.b"]
    # CLI argv plumbing (in-process main(), no subprocess).
    assert td.main([str(tmp_path / "cli.json"), p0, p1,
                    "--offset", "1:2000"]) == 0
    assert json.load(open(tmp_path / "cli.json"))["traceEvents"]


def test_tracedump_rejects_non_dump_input(tmp_path):
    bad = tmp_path / "notadump.jsonl"
    bad.write_text('["just", "a", "row"]\n')
    with pytest.raises(ValueError, match="meta"):
        telemetry.load_trace_jsonl(str(bad))


# -------------------------------------------------- export params + filenames

def test_export_chrome_trace_pid_and_offset_params(tmp_path):
    telemetry.enable()
    with telemetry.span("shifted"):
        pass
    base = json.load(open(telemetry.export_chrome_trace(
        str(tmp_path / "a.json"))))
    moved = json.load(open(telemetry.export_chrome_trace(
        str(tmp_path / "b.json"), pid=77, clock_offset_ns=2_000_000)))
    ev0 = next(e for e in base["traceEvents"] if e["ph"] == "X")
    ev1 = next(e for e in moved["traceEvents"] if e["ph"] == "X")
    assert ev0["pid"] == os.getpid() and ev1["pid"] == 77
    assert all(e["pid"] == 77 for e in moved["traceEvents"])   # M events too
    assert ev1["ts"] - ev0["ts"] == pytest.approx(2000.0)      # ns -> µs
    assert ev1["dur"] == ev0["dur"]


def test_trace_holds_host_spans_in_the_profilers_own_trace(tmp_path):
    """One clock: an enabled span is a ``TraceAnnotation``, so the profiler's
    own trace carries it in its host plane (with its nesting), and no second
    ``host_spans_w<id>.json`` file is written beside it."""
    import glob

    from jax.profiler import ProfileData

    from autodist_tpu.utils import tracing
    with tracing.trace("cluster_t", trace_dir=str(tmp_path),
                       with_host_spans=True):
        with telemetry.span("in.window"):
            with telemetry.span("in.window.child"):
                time.sleep(0.002)
    assert not telemetry.enabled()             # back to its prior state
    assert not glob.glob(str(tmp_path / "host_spans*"))
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    events = {e.name: e
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("in.window")}
    assert set(events) == {"in.window", "in.window.child"}
    outer, inner = events["in.window"], events["in.window.child"]
    assert inner.duration_ns >= 2e6
    assert outer.start_ns <= inner.start_ns
    assert (inner.start_ns + inner.duration_ns
            <= outer.start_ns + outer.duration_ns)
    # The ring holds the same spans (the exporters' source is unchanged).
    assert {s[0] for s in telemetry.snapshot_spans()} == set(events)


# ------------------------------------------------------ compile/memory gauges

def test_compile_signature_and_probe_counters():
    """The runner-side compile telemetry, without compiling anything: a new
    dispatch signature routes through _CompileProbe (bumping jit.cache_miss
    and jit.compile_s), a repeated one returns a plain span."""
    from autodist_tpu.runner import (DistributedRunner, _CompileProbe,
                                     _StepAnnotated)

    import weakref

    telemetry.enable()
    r = DistributedRunner.__new__(DistributedRunner)   # no mesh/model needed
    r._compile_sigs = set()
    r._fetch_tokens = weakref.WeakKeyDictionary()
    r._fetch_token_next = 0
    batch = {"x": np.zeros((4, 2), np.float32)}
    misses = telemetry.counter("jit.cache_miss")
    secs = telemetry.counter("jit.compile_s")
    before, before_s = misses.value, secs.value

    # Enabled mode: the probe (or the plain span) sits inside the step's
    # StepTraceAnnotation, whose number counts the annotated steps.
    cm = r._dispatch_span("runner.run.dispatch", "step", None, batch)
    assert isinstance(cm, _StepAnnotated)
    assert isinstance(cm._inner, _CompileProbe)
    with cm:
        time.sleep(0.002)
    assert misses.value == before + 1
    assert secs.value > before_s

    again = r._dispatch_span("runner.run.dispatch", "step", None, batch)
    assert not isinstance(again._inner, _CompileProbe)  # cached signature
    assert misses.value == before + 1
    # A different shape is a new signature -> a new probe.
    other = r._dispatch_span("runner.run.dispatch", "step", None,
                             {"x": np.zeros((8, 2), np.float32)})
    assert isinstance(other._inner, _CompileProbe)
    assert r._annotated_steps == 3
    # jit.compile spans carry the signature digest.
    jc = [s for s in telemetry.snapshot_spans() if s[0] == "jit.compile"]
    assert jc and "sig" in jc[-1][4]

    # Fetch-fn tokens are never reused: a new fn after the old one died
    # gets a fresh token (a recycled id() would alias the signatures).
    f1 = lambda p, b: p  # noqa: E731
    tok1 = r._fetch_token(f1)
    del f1
    f2 = lambda p, b: b  # noqa: E731
    assert r._fetch_token(f2) != tok1

    telemetry.disable()
    null = r._dispatch_span("runner.run.dispatch", "step", None, batch)
    from autodist_tpu.telemetry.spans import _NULL_SPAN
    assert null is _NULL_SPAN                          # disabled: no-op CM


def test_sample_device_memory_sets_gauges():
    telemetry.enable()
    keep = np.ones(8)     # host array; live_arrays() counts jax arrays only
    import jax
    dev = jax.device_put(np.ones((16,), np.float32))
    n = telemetry.sample_device_memory()
    assert n >= 2
    snap = telemetry.snapshot()
    assert snap["device.live_buffers"] >= 1
    assert snap["device.live_bytes"] >= dev.nbytes
    del keep, dev
