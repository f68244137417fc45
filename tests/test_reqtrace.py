"""Request-scoped distributed tracing (PR 19): reqtrace ring, wire-propagated
trace context, p99 exemplars, and adtrace.

NAMED to sort inside the tier-1 alphabetical window (next to the serve
tests). No subprocesses: fleets are in-process ``InferenceServer`` replicas
behind a real ``RouterServer`` over loopback (the test_serve_fleet
topology), so the process-global lifecycle ring sees every hop — router and
replica marks join on the router-scope rid exactly as they do across real
processes, minus the clock skew (pinned separately via ``ntp_offset``).

Coverage per the PR 19 contract:
- DISARMED is the production default and costs one attribute read: no ring
  growth, no clock read, no lock (the spans-contract twin, test-pinned);
- the ring is bounded and columnar; ``group_records`` orders per-rid marks;
- the trace-context token rides the existing generate framing: the replica
  decomposes WIRE time from queue time via the router-estimated clock
  offset (``cluster.ntp_offset`` rebasing pinned with a synthetic skew);
- a replayed request keeps its rid with a bumped hop — one trace, a
  visible failover (marks + Chrome-trace instant + both flow-id hops);
- ``serve.latency_s.total`` carries a slowest-in-window exemplar (rid +
  phase breakdown) that a firing ``serve_p99_burn`` books into the alert
  record, ``active()``, and the flight-recorder manifest — and the adtrace
  waterfall names decode on the guilty replica (the e2e acceptance pin);
- fleet merge is deterministic; the merged Chrome trace is schema-valid
  JSON with paired flow halves; reqtrace JSONL dumps round-trip;
- the ``serve.request`` span carries the rid (the span-args bugfix);
- adtop's ``req`` line and adfleet's ``attr`` column render the
  attribution gauges and the booked exemplar;
- the new env flags are registered (GL007's runtime face).
"""

import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from autodist_tpu import const, telemetry  # noqa: E402
from autodist_tpu.serving import (Batcher, InferenceServer,  # noqa: E402
                                  Router, RouterServer, ServeClient,
                                  ServeConfig, default_buckets)
from autodist_tpu.telemetry import alerts, cluster, history  # noqa: E402
from autodist_tpu.telemetry import metrics, recorder  # noqa: E402
from autodist_tpu.telemetry import reqtrace  # noqa: E402


# ------------------------------------------------------------------ fixtures

@pytest.fixture(autouse=True)
def _reqtrace_reset():
    """Leave the process-global planes as found: ring empty and DISARMED,
    no alert engine, no history, span ring empty (instruments stay — the
    registry is additive-only and shared across the suite)."""
    def reset():
        reqtrace.disable()
        reqtrace.clear()
        alerts.set_engine(None)
        history.set_history(None)
        telemetry.disable()
        telemetry.clear()
    reset()
    yield
    reset()


class FakeEngine:
    """Deterministic jax-free engine (the test_serve_fleet pattern): token =
    100*slot + step index; optional per-step delay so decode takes real
    wall time (the slow-replica and kill legs need requests in flight)."""

    def __init__(self, capacity=2, max_len=32, step_s=0.0):
        self.capacity = capacity
        self.max_len = max_len
        self.buckets = default_buckets(max_len)
        self.admits = []
        self._steps = np.zeros(capacity, np.int64)
        self.step_s = step_s

    def make_keys(self, seed, n):
        return None

    def admit(self, slot, prompt, key):
        self.admits.append((slot, int(prompt.size)))
        self._steps[slot] = 0
        return 100 * slot

    def step(self, keys):
        if self.step_s:
            time.sleep(self.step_s)
        self._steps += 1
        return (100 * np.arange(self.capacity) + self._steps).astype(np.int32)

    def free(self, slot):
        pass


def _replica_factory(capacity=2, max_queue=8, step_s=0.0, fleet=None,
                     step_s_list=None):
    """Factory for in-process replicas; ``step_s_list`` hands each created
    replica its own per-step delay (first replica gets the first entry),
    ``fleet`` collects (engine, server) pairs in creation order."""
    def factory():
        delay = step_s
        if step_s_list:
            delay = step_s_list.pop(0)
        engine = FakeEngine(capacity=capacity, step_s=delay)
        server = InferenceServer(
            Batcher(engine, ServeConfig(max_batch=capacity,
                                        max_queue=max_queue)), port=0)
        if fleet is not None:
            fleet.append((engine, server))
        return server
    return factory


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(os.path.dirname(__file__), os.pardir,
                           "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fleet_marks():
    """The in-process fleet's marks grouped per rid (one process-global
    ring — router and replica marks already share it)."""
    return reqtrace.group_records(reqtrace.snapshot_marks())


# ------------------------------------------------- ring + disarmed contract

def test_disarmed_mark_is_one_attribute_read(monkeypatch):
    """DISARMED (the production default) a mark must return after the one
    ``enabled`` attribute check: no clock read, no lock, no ring append.
    Pinned by making the clock and the lock explode — the disarmed path
    must never reach either."""
    assert not reqtrace.enabled()

    def boom(*a, **kw):
        raise AssertionError("disarmed mark touched the armed path")

    class BoomLock:
        __enter__ = __exit__ = boom

    monkeypatch.setattr(reqtrace.time, "perf_counter_ns", boom)
    monkeypatch.setattr(reqtrace._STATE, "lock", BoomLock())
    reqtrace.mark("rid-0", "queued", depth=3)      # must not raise
    monkeypatch.undo()
    assert reqtrace.snapshot_marks() == []         # and recorded nothing
    # Armed, the same call records (and DOES read the clock).
    reqtrace.enable()
    reqtrace.mark("rid-0", "queued", depth=3)
    assert reqtrace.snapshot_marks() == [
        ("rid-0", "queued", pytest.approx(time.perf_counter_ns(), abs=5e9),
         {"depth": 3})]


def test_ring_bound_and_group_records(monkeypatch):
    monkeypatch.setattr(reqtrace, "_STATE", reqtrace._State(4))
    reqtrace.enable()
    for i in range(10):
        reqtrace.mark(f"r{i % 2}", "queued", i=i)
    marks = reqtrace.snapshot_marks()
    assert len(marks) == 4                         # bounded, oldest evicted
    assert [m[3]["i"] for m in marks] == [6, 7, 8, 9]
    grouped = reqtrace.group_records(marks)
    assert set(grouped) == {"r0", "r1"}
    for recs in grouped.values():                  # per-rid, time-ordered
        assert [t for _, t, _ in recs] == sorted(t for _, t, _ in recs)


def test_reqtrace_flags_registered():
    """GL007's runtime face: the new knobs are typed ENV members AND
    registered in KNOWN_FLAGS (adenv/doctor see them)."""
    assert "AUTODIST_REQTRACE" in const.KNOWN_FLAGS
    assert "AUTODIST_REQTRACE_RING" in const.KNOWN_FLAGS
    assert isinstance(const.ENV.AUTODIST_REQTRACE.val, bool)
    assert int(const.ENV.AUTODIST_REQTRACE_RING.val) >= 1


# ------------------------------------- clock rebase / wire decomposition

def test_ntp_offset_synthetic_skew_and_median_rejection():
    """The router-side estimate the replica decomposes wire time with: a
    remote clock 5ms ahead over a symmetric 1ms-each-way path comes back as
    +5ms (+-rtt/2); one delayed outlier exchange is rejected by the
    median."""
    skew, leg = 5_000_000, 1_000_000
    samples = []
    for i in range(3):
        t0 = i * 10_000_000
        samples.append((t0, t0 + leg + skew, t0 + 2 * leg))
    off, err = cluster.ntp_offset(samples)
    assert off == skew
    assert err == leg
    # An asymmetric outlier (reply path stalled 50ms) would estimate the
    # offset 25ms off — the median across rounds ignores it.
    t0 = 90_000_000
    samples.append((t0, t0 + leg + skew, t0 + 2 * leg + 50_000_000))
    off, err = cluster.ntp_offset(samples)
    assert off == skew


def test_wire_time_decomposed_with_clock_offset(monkeypatch):
    """The replica rebases the token's origin send stamp through the
    router-estimated offset: with a forced -40ms offset (replica's clock
    behind) the decomposed wire time reads ~40ms above the true loopback
    wire; with the true (zero, shared-clock) offset it reads ~0."""
    from autodist_tpu.serving.router import Replica
    reqtrace.enable()
    router = Router(_replica_factory(), n_replicas=1, start=False)
    server = RouterServer(router)
    try:
        client = ServeClient(server.address)
        client.generate(np.arange(1, 4), 2, seed=0)
        monkeypatch.setattr(Replica, "clock_offset_ns",
                            lambda self: -40_000_000)
        client.generate(np.arange(1, 4), 2, seed=1)
    finally:
        server.close()
    wire_ns = [a["wire_ns"] for rid, recs in _fleet_marks().items()
               for p, _, a in recs if p == "received" and "wire_ns" in a]
    assert len(wire_ns) == 2
    assert 0 <= wire_ns[0] < 30_000_000            # shared clock: ~loopback
    assert wire_ns[1] >= 40_000_000                # rebased through -40ms
    assert wire_ns[1] < 90_000_000


# ------------------------------------------------------- fleet lifecycle

def test_fleet_lifecycle_marks_and_adtrace_report(tmp_path):
    """One armed request through a real RouterServer books the full
    lifecycle under ONE rid; adtrace renders the phase table and a
    waterfall naming the replica; the merged Chrome trace is schema-valid
    with PAIRED flow halves."""
    reqtrace.enable()
    router = Router(_replica_factory(), n_replicas=2, start=False)
    server = RouterServer(router)
    try:
        for i in range(3):
            ServeClient(server.address).generate(np.arange(1, 5), 3, seed=i)
    finally:
        server.close()
    grouped = _fleet_marks()
    rids = [r for r in grouped if str(r).startswith("router-")]
    assert len(rids) == 3
    phases = [p for p, _, _ in grouped[rids[0]]]
    # Router + replica marks joined on the rid, in causal order ("received"
    # appears twice: hop 0 at the router, then at the replica with wire_ns).
    for want in ("received", "sent", "queued", "admitted", "prefill_start",
                 "prefill_end", "first_token", "done", "finished"):
        assert want in phases, (want, phases)
    assert phases.index("sent") < phases.index("queued")
    assert phases.index("done") < phases.index("finished")
    sent = next(a for p, _, a in grouped[rids[0]] if p == "sent")
    assert sent["hop"] == 0
    assert sent["replica"] in {r.name for r in router.replicas()}

    adtrace = _load_tool("adtrace")
    states = [telemetry.local_reqtrace_state()]
    report = adtrace.render_report(states, top=2)
    for needle in ("queue", "decode", "total", str(rids[0]), "replica="):
        assert needle in report, (needle, report)

    out = str(tmp_path / "fleet.json")
    adtrace.write_chrome_trace(out, states)
    doc = json.load(open(out))
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert {e["ph"] for e in events} >= {"M", "X", "s", "f"}
    for e in events:
        assert {"ph", "pid", "tid"} <= set(e)
        if e["ph"] in ("X", "s", "f", "i"):
            assert isinstance(e["ts"], float)
        if e["ph"] == "X":
            assert e["dur"] >= 0
    # Every flow start the router stamped has its replica-side finish.
    s_ids = sorted(e["id"] for e in events if e["ph"] == "s")
    f_ids = sorted(e["id"] for e in events if e["ph"] == "f")
    assert s_ids and s_ids == f_ids
    assert "decode" in {e["name"] for e in events if e["ph"] == "X"}


def test_replay_keeps_rid_with_bumped_hop():
    """Kill a replica with requests in flight: the replayed request's marks
    stay under ONE rid — a 'replayed' instant plus a second 'sent' with a
    bumped hop — so the trace shows the failover instead of losing the
    request at the dead replica."""
    reqtrace.enable()
    Router_backoff = Router.RESPAWN_BACKOFF_S
    Router.RESPAWN_BACKOFF_S = 0.02
    fleet = []
    router = Router(_replica_factory(step_s=0.01, fleet=fleet),
                    n_replicas=2, start=False)
    server = RouterServer(router)
    try:
        victim = router.replicas()[0]

        def killer():
            deadline = time.monotonic() + 5.0
            while victim.in_flight == 0 and time.monotonic() < deadline:
                time.sleep(0.001)
            victim.server.kill()

        errors = []

        def one(i):
            try:
                ServeClient(server.address).generate(np.arange(1, 4), 8,
                                                     seed=i)
            except Exception as e:   # noqa: BLE001 - the assert reports it
                errors.append(repr(e))

        kt = threading.Thread(target=killer)
        kt.start()
        threads = [threading.Thread(target=one, args=(i,)) for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        kt.join()
        assert errors == []
    finally:
        server.close()
        Router.RESPAWN_BACKOFF_S = Router_backoff
    replayed = {rid: recs for rid, recs in _fleet_marks().items()
                if any(p == "replayed" for p, _, _ in recs)}
    assert replayed, "the kill never landed mid-flight"
    rid, recs = next(iter(replayed.items()))
    hops = [a["hop"] for p, _, a in recs if p == "sent"]
    assert sorted(hops) == list(range(len(hops))) and len(hops) >= 2
    assert any(p == "finished" for p, _, _ in recs)   # same rid completed
    # The failover renders: one rid, a replay instant, both flow hops.
    events = cluster.reqtrace_trace_events(
        telemetry.local_reqtrace_state(), pid=0, origin_ns=0)
    mine = [e for e in events
            if e.get("args", {}).get("rid") == str(rid)
            or str(e.get("id", "")).startswith(f"{rid}/")]
    assert any(e["ph"] == "i" and e["name"] == "replayed" for e in mine)
    flow_hops = {e["id"] for e in mine if e["ph"] == "s"}
    assert {f"{rid}/0", f"{rid}/1"} <= flow_hops


# ----------------------------------------- exemplars + the e2e burn pin

def test_histogram_exemplar_slowest_in_window():
    reg = metrics.Registry()
    h = reg.histogram("rt.lat", buckets=(0.1, 1.0))
    assert h.exemplar() is None
    h.observe(0.5, exemplar={"rid": "a"})
    h.observe(0.2, exemplar={"rid": "b"})          # faster: not booked
    assert h.exemplar() == {"rid": "a", "value": 0.5}
    h.observe(0.9, exemplar={"rid": "c"})          # slower: replaces
    assert h.exemplar()["rid"] == "c"
    h.observe(2.0)                                 # no exemplar offered
    assert h.exemplar()["rid"] == "c"
    # The exemplar stays OUT of snapshots (deterministic exposition).
    assert "exemplar" not in json.dumps(reg.snapshot())
    # Window expiry: a stale exemplar stops answering and any fresh
    # observation may rebook, even a faster one.
    h._ex_t -= metrics.EXEMPLAR_WINDOW_S + 1
    assert h.exemplar() is None
    h.observe(0.1, exemplar={"rid": "d"})
    assert h.exemplar()["rid"] == "d"


class _WindowClock:
    """``time`` for the metrics history with ``monotonic`` in the test's
    hands: the burn-rate windows are then counted in the seconds the test
    says a storm took, not in what a loaded machine made of it."""

    def __init__(self):
        self.now = time.monotonic()

    def monotonic(self):
        return self.now

    def __getattr__(self, name):
        return getattr(time, name)


def test_p99_burn_books_exemplar_and_adtrace_names_guilty_replica(
        tmp_path, monkeypatch):
    """The PR's e2e acceptance pin: one SLOW replica in a 2-replica fleet
    drives serve.latency_s.total's p99 over a tight SLO; the firing
    serve_p99_burn books the slowest request's exemplar (rid + phase
    breakdown) into the alert record, ``active()``, and the flight-recorder
    manifest; adtrace's waterfall for that rid names decode on the guilty
    replica."""
    reqtrace.enable()
    # The windows' clock is injected: each storm books 0.5 s on it (what it
    # takes unloaded), so both windows hold two samples however long the
    # machine took. The latencies the rule reads stay real.
    clock = _WindowClock()
    monkeypatch.setattr(history, "time", clock)
    rule = alerts.AlertRule(name="serve_p99_burn", kind="burn_rate",
                            metric="serve.latency_s.total", q=0.99,
                            objective_s=0.05, long_s=1.2, short_s=0.6)
    eng = alerts.AlertEngine(rules=[rule], action="warn")
    alerts.set_engine(eng)
    h = history.MetricsHistory(out_dir="", min_interval_s=0.0)
    # The baseline must hold the histogram (a delta needs both ends): it
    # exists already when an earlier test of this file served a request,
    # and this test does not lean on that.
    latency = telemetry.histogram("serve.latency_s.total")
    # The registry is shared across the suite and the histogram keeps the
    # slowest exemplar of the last 300 s: a slower request of an earlier file
    # on the same worker (tests/test_serve_fleet.py) would be the one the
    # alert names. Start without one.
    with latency._lock:
        latency._ex, latency._ex_value = None, 0.0
    h.sample()                                     # window-opening baseline

    fleet = []
    router = Router(_replica_factory(step_s_list=[0.08, 0.0], fleet=fleet),
                    n_replicas=2, start=False)
    server = RouterServer(router)
    try:
        slow_name = "%s:%d" % fleet[0][1].address
        assert fleet[0][0].step_s == 0.08

        def storm():
            # 4 concurrent requests over 2x capacity-2 replicas: the
            # least-loaded spread parks two on the slow one (0.48s decode)
            # and two on the fast one (~0) — the slowest IS the exemplar.
            threads = [threading.Thread(
                target=lambda i=i: ServeClient(server.address).generate(
                    np.arange(1, 4), 6, seed=i)) for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            clock.now += 0.5

        storm()                                    # burns the long window...
        h.sample()
        eng.evaluate(h)                            # ...maybe short on span
        storm()
        h.sample()
        fired = [f for f in eng.evaluate(h) + eng.active()
                 if f["rule"] == "serve_p99_burn"]
    finally:
        server.close()

    assert fired, "serve_p99_burn never fired"
    ex = fired[0].get("exemplar")
    assert ex is not None, fired[0]
    assert str(ex["rid"]).startswith("router-")
    assert ex["total_s"] >= 0.4                    # the slow replica's work
    assert ex["decode_s"] >= 0.8 * ex["total_s"]   # phase breakdown rides
    # ...into the flight-recorder manifest (the non-creating accessor).
    manifest = recorder.build_manifest("test")
    booked = [a for a in manifest.get("alerts", ())
              if a.get("rule") == "serve_p99_burn"]
    assert booked and booked[0]["exemplar"]["rid"] == ex["rid"]

    # adtrace: the booked rid's trace pins decode as the dominant phase ON
    # the slow replica — the alert names a request, the trace names why.
    adtrace = _load_tool("adtrace")
    grouped = _fleet_marks()
    recs = grouped[ex["rid"]]
    assert next(a for p, _, a in recs
                if p == "sent")["replica"] == slow_name
    durations = adtrace.phase_durations(reqtrace.snapshot_marks())
    decode = dict((rid, s) for s, rid in durations["decode"])
    assert decode[ex["rid"]] >= 0.4
    report = adtrace.render_report([telemetry.local_reqtrace_state()],
                                   top=8)
    assert str(ex["rid"]) in report
    assert f"replica={slow_name}" in report


# ------------------------------------- merge determinism + offline dumps

def _synthetic_ring():
    reqtrace.enable()
    t = [0]

    def tick(rid, phase, **args):
        reqtrace.mark(rid, phase, **args)
    tick("r-1", "received", hop=0)
    tick("r-1", "sent", replica="a:1", hop=0, send_wall_ns=123)
    tick("r-1", "received", hop=0, wire_ns=250_000)
    tick("r-1", "queued", depth=1)
    tick("r-1", "admitted", slot=0)
    tick("r-1", "prefill_start", prompt_len=4)
    tick("r-1", "prefill_end")
    tick("r-1", "first_token")
    tick("r-2", "shed", reason="fleet_busy")
    tick("r-1", "done", tokens=3)
    tick("r-1", "finished", replica="a:1")
    del t


def test_merge_determinism_and_jsonl_roundtrip(tmp_path):
    """Same blobs in -> byte-identical Chrome trace out (twice); a reqtrace
    JSONL dump loads back into the same rebased marks, and tracedump merges
    it offline into the same flow-linked timeline."""
    _synthetic_ring()
    state = telemetry.local_reqtrace_state(worker_id=7)
    p1, p2 = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    cluster.merge_trace_states([], p1, reqtrace_states=[state])
    cluster.merge_trace_states([], p2, reqtrace_states=[state])
    b1, b2 = open(p1, "rb").read(), open(p2, "rb").read()
    assert b1 == b2 and b1                         # deterministic merge

    dump = str(tmp_path / "req.jsonl")
    telemetry.dump_reqtrace_jsonl(dump, worker_id=7)
    loaded = telemetry.load_reqtrace_jsonl(dump)
    # Lossless round-trip: identical records; the absolute wall stamps may
    # jitter by the dump's own back-to-back wall/perf pair (sub-us).
    got, want = cluster.reqtrace_marks(loaded), cluster.reqtrace_marks(state)
    assert ([(m["rid"], m["phase"], m["args"]) for m in got]
            == [(m["rid"], m["phase"], m["args"]) for m in want])
    assert all(abs(g["wall_ns"] - w["wall_ns"]) < 1_000_000
               for g, w in zip(got, want))
    with pytest.raises(ValueError, match="reqtrace"):
        bad = tmp_path / "spans.jsonl"
        bad.write_text('{"meta": {"kind": "spans"}}\n')
        telemetry.load_reqtrace_jsonl(str(bad))

    tracedump = _load_tool("tracedump")
    p3 = str(tmp_path / "c.json")
    tracedump.merge_dumps(p3, [], reqtrace_files=[dump])
    doc = json.load(open(p3))
    names = {e.get("name") for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"queue", "prefill", "decode", "route", "wire"} <= names
    assert any(e["ph"] == "i" and e["name"] == "shed"
               for e in doc["traceEvents"])


def test_reqtrace_pull_opcode_and_dedupe(tmp_path):
    """Both server kinds answer the ``reqtrace`` pull; adtrace collapses the
    in-process fleet's identical ring blobs to one per OS process before
    merging (no triple-counted marks)."""
    reqtrace.enable()
    router = Router(_replica_factory(), n_replicas=1, start=False)
    server = RouterServer(router)
    try:
        ServeClient(server.address).generate(np.arange(1, 4), 2, seed=0)
        adtrace = _load_tool("adtrace")
        addrs = ["%s:%d" % server.address,
                 router.replicas()[0].name]
        pulled = adtrace.collect(addrs)
        assert not pulled["errors"]
        states = pulled["states"]
        assert len(states) == 2                    # one blob per endpoint...
        assert len(adtrace.dedupe_states(states)) == 1   # ...one process
        n_marks = len(adtrace.merged_marks(states))
        assert n_marks == len(reqtrace.snapshot_marks())
    finally:
        server.close()


# --------------------------------------------------- spans + console lines

def test_serve_request_span_carries_rid():
    """The span-args bugfix: the replica's serve.request span names BOTH
    its local rid and the router-scope rid token, so a span ring pulled
    from one replica joins the fleet-wide trace."""
    telemetry.enable()
    router = Router(_replica_factory(), n_replicas=1, start=False)
    server = RouterServer(router)
    try:
        ServeClient(server.address).generate(np.arange(1, 4), 2, seed=0)
    finally:
        server.close()
    spans = [(name, args) for name, _, _, _, args in
             telemetry.snapshot_spans() if name == "serve.request"]
    tokens = [a.get("rid_token") for _, a in spans if a and "rid_token" in a]
    assert tokens and all(str(t).startswith("router-") for t in tokens)
    assert any(a and "rid" in a for _, a in spans)


def test_consoles_render_attr_shares_and_exemplar():
    adtop = _load_tool("adtop")
    reg = {"serve.attr.wire": 0.02, "serve.attr.queue": 0.1,
           "serve.attr.prefill": 0.18, "serve.attr.decode": 0.7}
    lines = adtop._req_lines(reg, {"active": [
        {"rule": "serve_p99_burn", "exemplar": {"rid": "router-3"}}]})
    assert len(lines) == 1
    assert "attr" in lines[0] and "decode .70" in lines[0]
    assert "exemplar router-3 (serve_p99_burn)" in lines[0]
    assert adtop._req_lines({}, {}) == []          # un-armed: line off

    adfleet = _load_tool("adfleet")
    row = adfleet._row("x:1", {"kind": "serve", "uptime_s": 5,
                               "capacity": 2, "queue_depth": 0,
                               "registry": reg})
    assert "attr w.02/q.10/p.18/d.70" in row
    bare = adfleet._row("x:1", {"kind": "serve", "uptime_s": 5,
                                "capacity": 2, "queue_depth": 0,
                                "registry": {}})
    assert "attr" not in bare


def test_attr_gauges_sum_to_one_per_round():
    """serve.attr.* (the serving twin of train.attr.*): after served
    traffic the per-round shares exist and sum to ~1.0."""
    reqtrace.enable()
    router = Router(_replica_factory(), n_replicas=1, start=False)
    server = RouterServer(router)
    try:
        for i in range(3):
            ServeClient(server.address).generate(np.arange(1, 5), 3, seed=i)
        deadline = time.monotonic() + 2.0
        shares = {}
        while time.monotonic() < deadline:
            snap = telemetry.snapshot()
            shares = {p: snap.get(f"serve.attr.{p}")
                      for p in ("wire", "queue", "prefill", "decode")}
            if all(isinstance(v, (int, float)) for v in shares.values()):
                break
            time.sleep(0.01)
    finally:
        server.close()
    assert all(isinstance(v, (int, float)) for v in shares.values()), shares
    assert sum(shares.values()) == pytest.approx(1.0, abs=0.01)
