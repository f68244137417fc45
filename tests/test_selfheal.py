"""Self-healing runtime: the detect→act loop, driven by REAL injected faults.

Covers the recovery plane end to end (docs/usage/resilience.md):

- deterministic fault points (``testing/faults.py``): spec parsing, exact
  step/worker keying, count-bounded consumption under concurrency;
- wire-level retry: injected connect refusals and mid-call resets retry
  IDEMPOTENT opcodes with jittered backoff, surface non-idempotent ones;
- auto-eviction: a sustained stall past ``AUTODIST_EVICT_AFTER_S`` retires
  the worker from the staleness gate (one deterministic watchdog tick), the
  gate unwedges, a parked gate RPC fails typed (``WorkerEvicted``);
- rejoin with catch-up: an evicted remote worker auto-rejoins seeded at the
  slowest live count and pulls the chief's LIVE params over ``read_min``; a
  crashed worker's replacement continues BIT-IDENTICALLY vs an unfailed run;
- recover action: injected NaN under ``AUTODIST_HEALTH_ACTION=recover``
  rolls back to the last-known-good snapshot and the run FINISHES with
  finite (and bit-identical, callable-source) params; ``AUTODIST_RECOVER_
  MAX`` exhaustion escalates to the existing :class:`HealthHalt`;
- the coordinator's ``AUTODIST_WORKER_FAILURE=respawn`` policy (budgeted,
  backed-off relaunch instead of ``os._exit(1)``);
- the ``status`` opcode's ``recovery`` section + adtop/adfleet rendering;
- the new flag registrations.

Pure in-process host tests — no subprocess spawns; sorts after the tier-1
window edge and stays cheap (tiny scalar/linear models, bounded waits only).
"""

import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from autodist_tpu import AutoDist, const, telemetry, train  # noqa: E402
from autodist_tpu.parallel import recovery  # noqa: E402
from autodist_tpu.parallel.staleness import (ParameterService,  # noqa: E402
                                             StalenessController,
                                             WorkerEvicted)
from autodist_tpu.runner import TrainState  # noqa: E402
from autodist_tpu.strategy import PS, AllReduce  # noqa: E402
from autodist_tpu.telemetry import health  # noqa: E402
from autodist_tpu.testing import faults  # noqa: E402


@pytest.fixture(autouse=True)
def _disarm_faults():
    """Every test starts and ends with the fault harness disarmed — an
    armed plan leaking across tests would fire in an unrelated step loop."""
    faults.clear()
    yield
    faults.clear()


# ------------------------------------------------------------------ fixtures

BATCH = 16


def _ps_data(seed=3):
    rng = np.random.RandomState(seed)
    x = rng.randn(BATCH).astype(np.float32)
    return {"x": x, "y": (2.0 * x - 1.0).astype(np.float32)}


def _ps_loss(p, b):
    return jnp.mean((b["y"] - (b["x"] * p["w"] + p["b"])) ** 2)


def _ps_params():
    return {"w": np.zeros((), np.float32), "b": np.zeros((), np.float32)}


def _ps_session(num_workers=2, staleness=2):
    # staleness=0 needs sync=False to select the async (fully unbounded)
    # regime; staleness>0 is bounded-stale with the default sync flag.
    ad = AutoDist(strategy_builder=PS(sync=staleness > 0,
                                      staleness=staleness))
    runner = ad.create_distributed_session(
        _ps_loss, _ps_params(), optax.sgd(0.05), example_batch=_ps_data(),
        num_workers=num_workers)
    runner.init(_ps_params())
    return runner


class _StubPSRunner:
    """The minimal surface PSServer._dispatch drives (the test_health_plane
    pattern): a real gate + numpy-only ParameterService, no compilation."""

    def __init__(self, num_workers=2, staleness=1):
        state = TrainState(step=np.zeros((), np.int32),
                           params={"w": np.ones((8,), np.float32)},
                           opt_state=(), ef_state=())
        self.service = ParameterService(state, lambda s, grads: s)
        self.controller = StalenessController(num_workers,
                                              staleness=staleness)

    def add_worker(self, worker_id=None, with_generation=False):
        wid, gen = self.controller.register_with_generation(worker_id)
        handle = type("H", (), {"worker_id": wid})()
        return (handle, gen) if with_generation else handle


def _loopback_stub(num_workers=2, staleness=1):
    from autodist_tpu.parallel.ps_transport import PSServer
    server = PSServer(_StubPSRunner(num_workers, staleness),
                      host="127.0.0.1", watchdog=False)
    return server, "%s:%d" % server.address


def _loss(p, b):
    return jnp.mean((b["y"] - b["x"] @ p["w"]) ** 2)


def _params():
    return {"w": np.random.RandomState(0).randn(4, 1).astype(np.float32)}


def _batch(i):
    rng = np.random.RandomState(100 + i)
    return {"x": rng.randn(32, 4).astype(np.float32),
            "y": rng.randn(32, 1).astype(np.float32)}


@pytest.fixture(scope="module")
def ar_runner():
    """One compiled AllReduce session shared by the recover-action tests
    (train() re-inits per call; the jit cache is what's being shared)."""
    ad = AutoDist(strategy_builder=AllReduce())
    return ad.create_distributed_session(
        _loss, _params(), optax.adam(1e-2), example_batch=_batch(0),
        health=True)


# ------------------------------------------------------------- fault harness

def test_fault_spec_parse_roundtrip():
    pts = faults.parse("worker_crash@step=3,worker=1;nan_grads@step=5;"
                       "wire_refuse@count=2;worker_hang@for_s=0.25,worker=0")
    assert [p.kind for p in pts] == ["worker_crash", "nan_grads",
                                    "wire_refuse", "worker_hang"]
    assert pts[0].step == 3 and pts[0].worker == 1 and pts[0].count == 1
    assert pts[2].count == 2
    assert pts[3].for_s == 0.25
    with pytest.raises(ValueError, match="unknown fault kind"):
        faults.parse("explode@step=1")
    with pytest.raises(ValueError, match="unknown key"):
        faults.parse("nan_grads@steps=1")


def test_fault_should_fire_is_deterministic_and_consumed():
    faults.install("worker_crash@step=3,worker=1;wire_refuse@count=2")
    assert faults.armed()
    # Wrong step / wrong worker never fire.
    assert not faults.should_fire("worker_crash", step=2, worker=1)
    assert not faults.should_fire("worker_crash", step=3, worker=0)
    assert faults.should_fire("worker_crash", step=3, worker=1)
    # Consumed: the exact same key cannot fire twice past its count.
    assert not faults.should_fire("worker_crash", step=3, worker=1)
    # Count-bounded under concurrency: 8 threads race for 2 firings.
    hits = []
    def probe():
        if faults.should_fire("wire_refuse"):
            hits.append(1)
    threads = [threading.Thread(target=probe) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=5)
    assert len(hits) == 2
    faults.clear()
    assert not faults.armed()


def test_fault_hang_returns_bounded_duration_and_consumes():
    faults.install("worker_hang@step=2,worker=0,for_s=0.25;"
                   "worker_hang@worker=1,for_s=0.1,count=2")
    assert faults.hang_s(step=1, worker=0) == 0.0    # wrong step: no hang
    assert faults.hang_s(step=2, worker=0) == 0.25
    assert faults.hang_s(step=2, worker=0) == 0.0    # consumed
    assert faults.hang_s(step=9, worker=1) == 0.1    # step-agnostic point
    assert faults.hang_s(step=3, worker=1) == 0.1
    assert faults.hang_s(step=4, worker=1) == 0.0    # count=2 spent


def test_fault_corrupt_batch_nanifies_floats_only():
    b = {"x": np.ones((4, 2), np.float32), "ids": np.arange(4),
         "flag": np.array([True, False])}
    c = faults.corrupt_batch(b)
    assert np.isnan(c["x"]).all()
    assert np.array_equal(c["ids"], b["ids"])
    assert np.array_equal(c["flag"], b["flag"])


# ---------------------------------------------------------------- wire retry

def test_wire_refuse_connect_retries_then_connects():
    from autodist_tpu.parallel.ps_transport import _PSClient
    server, addr = _loopback_stub()
    try:
        faults.install("wire_refuse@count=2")
        client = _PSClient(addr, connect_timeout=10.0)
        assert faults.points()[0].fired == 2   # both refusals consumed
        assert client.call("version")[0] == 0
        client.close()
    finally:
        server.close()


def test_wire_reset_retries_idempotent_surfaces_nonidempotent():
    from autodist_tpu.parallel.ps_transport import (IDEMPOTENT_OPS,
                                                    _PSClient, _retry_safe)
    # The idempotency table itself is part of the contract.
    assert "read" in IDEMPOTENT_OPS and "register" in IDEMPOTENT_OPS
    assert "apply" not in IDEMPOTENT_OPS
    assert "finish_step" not in IDEMPOTENT_OPS
    # register is replay-safe ONLY with an explicit id: register(None)
    # ALLOCATES a fresh slot per request, and a replay would leave a
    # phantom live slot pinning min(steps).
    assert _retry_safe(("register", 3))
    assert not _retry_safe(("register", None))
    assert not _retry_safe(("register",))
    assert not _retry_safe(("apply", {}))
    server, addr = _loopback_stub()
    try:
        client = _PSClient(addr, connect_timeout=10.0)
        faults.install("wire_reset@op=read")
        params, ef, version = client.call("read")   # transparent retry
        assert params is not None and version == 0
        assert faults.points()[0].fired == 1
        faults.install("wire_reset@op=apply")
        with pytest.raises(ConnectionResetError):
            client.call("apply", {"w": np.zeros((8,), np.float32)})
        client.close()
    finally:
        server.close()


def test_backoff_is_bounded_and_grows():
    delays = [recovery.backoff_s(a, 0.2, cap_s=5.0) for a in range(10)]
    assert all(0.0 <= d <= 5.0 for d in delays)
    # The exponential envelope: attempt 5's ceiling is the cap.
    assert recovery.backoff_s(0, 0.2, cap_s=5.0) <= 0.2
    assert recovery.backoff_s(50, 0.2, cap_s=5.0) <= 5.0
    assert recovery.backoff_s(0, 0.0) == 0.0


# ------------------------------------------------------------- auto-eviction

def test_watchdog_evicts_sustained_stall_and_gate_unwedges():
    from autodist_tpu.parallel.ps_transport import _StragglerWatchdog
    server, _ = _loopback_stub(num_workers=2, staleness=1)
    stub = server._runner
    evicted0 = telemetry.counter("recover.evicted").value
    try:
        # Worker 1 never steps: worker 0 runs to the bound then parks.
        stub.controller.start_step(0, timeout=1)
        stub.controller.finish_step(0)
        with pytest.raises(Exception):   # StalenessTimeout: parked at bound
            stub.controller.start_step(0, timeout=0.2)
        # Deterministic watchdog tick with worker 1 long silent.
        server._stats_for(0)
        server._stats_for(1)
        with server._worker_stats_lock:
            server._worker_stats[1].last_seen = time.monotonic() - 999.0
        wd = _StragglerWatchdog(server, interval=60.0, evict_after=30.0)
        try:
            wd._sample()
        finally:
            wd.close()
        assert 1 in stub.controller._retired
        assert telemetry.counter("recover.evicted").value == evicted0 + 1
        assert any(e["name"] == "recover.evicted"
                   for e in telemetry.events())
        # The gate unwedged: worker 0 steps freely past the old bound.
        for _ in range(3):
            stub.controller.start_step(0, timeout=1)
            stub.controller.finish_step(0)
        # status ships the recovery section with the eviction recorded.
        status = server.status_snapshot()
        assert status["recovery"]["counts"]["evicted"] >= 1
        assert any(r["worker"] == 1 and r["kind"] == "stall"
                   for r in status["recovery"]["evictions"])
    finally:
        server.close()


def test_eviction_wakes_parked_gate_wait_with_typed_error():
    c = StalenessController(num_workers=2, staleness=1)
    c.start_step(0, timeout=1)
    c.finish_step(0)    # worker 0 now AT the bound (worker 1 at 0)
    result = {}

    def parked():
        try:
            c.start_step(0, timeout=30)
        except BaseException as e:       # noqa: BLE001 — recorded for assert
            result["error"] = e
    t = threading.Thread(target=parked)
    t.start()
    time.sleep(0.1)          # let it park (bounded)
    c.retire(0)              # evict the PARKED worker: its RPC must fail NOW
    t.join(timeout=5)
    assert not t.is_alive()
    assert isinstance(result.get("error"), WorkerEvicted)
    # Entry case: an already-retired worker's start_step raises immediately.
    with pytest.raises(WorkerEvicted):
        c.start_step(0, timeout=1)
    # And a register re-admits it (the rejoin path's first half).
    c.register(0)
    c.start_step(0, timeout=1)
    c.finish_step(0)


# --------------------------------------------------- rejoin + crash recovery

def test_remote_worker_auto_rejoins_after_eviction():
    from autodist_tpu.parallel.ps_transport import PSServer, RemotePSWorker
    batch = _ps_data()
    runner = _ps_session(num_workers=2, staleness=2)
    server = PSServer(runner, host="127.0.0.1", watchdog=False)
    host, port = server.address
    rejoined0 = telemetry.counter("recover.rejoined").value
    remote = RemotePSWorker(f"{host}:{port}", runner, worker_id=1)
    try:
        remote.step(batch, timeout=10)
        # Chief-side eviction mid-run (what the watchdog does on a stall).
        recovery.evict(runner.controller, 1, kind="stall", age_s=42.0)
        # The next step hits WorkerEvicted, auto-rejoins seeded at the
        # slowest LIVE count, catches up over read_min, and completes.
        remote.step(batch, timeout=10)
        assert runner.service.updates_applied == 2
        assert telemetry.counter("recover.rejoined").value > rejoined0
        # The catch-up pull re-read live params (the cache was dropped at
        # rejoin, so a stale pre-eviction tree can never be revalidated).
        assert remote.last_version_read >= 1
    finally:
        remote.close()
        server.close()


def test_crash_respawn_readmin_catchup_bit_identical():
    """A worker crash mid-run + replacement with live-param catch-up must
    continue BIT-IDENTICALLY vs an unfailed run (single sequential pusher —
    the regime where async semantics allow exact comparison)."""
    from autodist_tpu.parallel.ps_transport import PSServer, RemotePSWorker
    batches = [_ps_data(seed=s) for s in range(6)]

    def run_leg(crash_at):
        runner = _ps_session(num_workers=1, staleness=0)
        server = PSServer(runner, host="127.0.0.1", watchdog=False)
        host, port = server.address
        if crash_at is not None:
            faults.install(f"worker_crash@step={crash_at},worker=0")
        worker = RemotePSWorker(f"{host}:{port}", runner, worker_id=0,
                                overlap=False)
        i = 0
        try:
            while i < len(batches):
                try:
                    worker.step(batches[i], timeout=10)
                    i += 1
                except faults.WorkerCrashed:
                    # The "coordinator respawn" in miniature: wait for the
                    # server to retire the dead connection, then a fresh
                    # RemotePSWorker re-registers and catches up over
                    # read_min — the crashed step i is retried by the
                    # replacement (it never reached the chief).
                    deadline = time.time() + 10
                    while 0 not in runner.controller._retired \
                            and time.time() < deadline:
                        time.sleep(0.02)
                    worker = RemotePSWorker(f"{host}:{port}", runner,
                                            worker_id=0, overlap=False)
        finally:
            faults.clear()
            worker.close()
            server.close()
        assert runner.service.updates_applied == len(batches)
        return jax.device_get(
            jax.tree_util.tree_leaves(runner.service.state.params))

    clean = run_leg(None)
    crashed = run_leg(3)
    assert all(np.array_equal(a, b) for a, b in zip(clean, crashed))
    assert all(np.isfinite(np.asarray(l)).all() for l in crashed)


# ------------------------------------------------------------ recover action

@pytest.mark.parametrize("unroll", [1, 2], ids=["per_step", "unrolled"])
def test_nan_recover_rolls_back_finishes_finite_and_bit_identical(ar_runner,
                                                                  unroll):
    """The fault fires where the batch of step 5 is pulled, so a block that
    holds that step is poisoned like the step alone."""
    rollbacks0 = telemetry.counter("recover.rollback").value
    monitor = health.HealthMonitor(health.HealthConfig(action="recover"))
    faults.install("nan_grads@step=5")
    final = train(ar_runner, _params(), _batch, steps=12, log_every=2,
                  health_monitor=monitor, unroll=unroll)
    assert faults.points()[0].fired == 1
    faults.clear()
    # (a) The run FINISHED (did not halt) with finite params.
    assert int(final.step) == 12
    leaves = jax.device_get(jax.tree_util.tree_leaves(final.params))
    assert all(np.isfinite(l).all() for l in leaves)
    # (b) Exactly the rollback machinery did it.
    assert telemetry.counter("recover.rollback").value > rollbacks0
    assert recovery.recovery_snapshot()["counts"]["rollbacks"] >= 1
    # (c) A callable source replays the rolled-back steps exactly: the
    # recovered run is BIT-IDENTICAL to a never-faulted one.
    clean = train(ar_runner, _params(), _batch, steps=12, log_every=2,
                  unroll=unroll)
    a = jax.device_get(jax.tree_util.tree_leaves(final.params))
    b = jax.device_get(jax.tree_util.tree_leaves(clean.params))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))


def test_recover_budget_is_per_incident_not_per_run(ar_runner, monkeypatch):
    """AUTODIST_RECOVER_MAX bounds attempts per INCIDENT: two transient
    anomalies at different steps each get the full budget (progress past
    the earlier one resets the counter) — a long run's widely-spaced
    recoveries must not spend a lifetime cap."""
    monkeypatch.setenv("AUTODIST_RECOVER_MAX", "1")
    monitor = health.HealthMonitor(health.HealthConfig(action="recover"))
    faults.install("nan_grads@step=3;nan_grads@step=8")
    final = train(ar_runner, _params(), _batch, steps=12, log_every=2,
                  health_monitor=monitor)
    faults.clear()
    assert int(final.step) == 12   # both incidents recovered
    leaves = jax.device_get(jax.tree_util.tree_leaves(final.params))
    assert all(np.isfinite(l).all() for l in leaves)


def test_retire_reports_whether_it_acted():
    """retire() returns True only for a live->retired transition — the
    recovery plane's bookkeeping follows it, so a stale-generation no-op or
    a double retire can never book a phantom eviction."""
    c = StalenessController(num_workers=2, staleness=1)
    old_gen = c.generation(1)
    c.register(1)                                   # generation bumps
    assert c.retire(1, generation=old_gen) is False  # stale: ignored
    assert c.retire(1) is True                       # acted
    assert c.retire(1) is False                      # already retired
    # evict() on an already-retired worker books nothing.
    before = telemetry.counter("recover.evicted").value
    assert recovery.evict(c, 1, kind="stall") is None
    assert telemetry.counter("recover.evicted").value == before


def test_recover_max_exhaustion_escalates_to_healthhalt(ar_runner,
                                                        monkeypatch):
    monkeypatch.setenv("AUTODIST_RECOVER_MAX", "2")
    monitor = health.HealthMonitor(health.HealthConfig(action="recover"))
    # A PERSISTENT fault (count=99): every replay re-poisons step 5.
    faults.install("nan_grads@step=5,count=99")
    with pytest.raises(telemetry.HealthHalt) as ei:
        train(ar_runner, _params(), _batch, steps=12, log_every=2,
              health_monitor=monitor)
    faults.clear()
    # The escalation is the EXACT halt type (not the recover subclass),
    # with the live state attached — checkpointable, not discarded.
    assert type(ei.value) is telemetry.HealthHalt
    assert ei.value.state is not None
    assert ei.value.anomalies


def test_recover_before_any_good_boundary_escalates(ar_runner):
    monitor = health.HealthMonitor(health.HealthConfig(action="recover"))
    faults.install("nan_grads@step=0,count=99")   # poisoned from step 0
    with pytest.raises(telemetry.HealthHalt):
        train(ar_runner, _params(), _batch, steps=6, log_every=2,
              health_monitor=monitor)
    faults.clear()


def test_snapshot_ring_bounds_and_checkout_copies():
    copies = []

    def copy_fn(state):
        copies.append(state)
        return dict(state)
    ring = recovery.SnapshotRing(keep=2, copy_fn=copy_fn)
    for step in (2, 4, 6):
        ring.push(step, {"step": step})
    assert len(ring) == 2                      # bounded
    assert ring.newest()[0] == 6
    step, state = ring.checkout()
    assert step == 6 and state == {"step": 6}
    assert state is not ring.newest()[1]       # checkout COPIES
    ring.push(6, {"step": 6, "replayed": True})
    assert len(ring) == 2                      # same-step push replaces
    assert ring.newest()[1]["replayed"]
    # Slow-burn fallback: dropping the suspect newest lands one deeper.
    ring.drop_newest()
    assert ring.checkout()[0] == 4
    ring.drop_newest()
    assert ring.checkout() is None             # empty -> escalation
    ring.drop_newest()                         # idempotent on empty
    assert recovery.SnapshotRing().checkout() is None


def test_alert_recover_action_raises_typed_signal():
    from autodist_tpu.telemetry import alerts as _alerts
    from autodist_tpu.telemetry import history as _history
    assert "recover" in _alerts.ACTIONS and "recover" in health.ACTIONS
    telemetry.gauge("selfheal.test.gauge").set(99.0)
    eng = _alerts.AlertEngine(rules=[_alerts.AlertRule(
        name="selfheal_pin", kind="threshold",
        metric="selfheal.test.gauge", op=">", value=1.0)], action="recover")
    h = _history.MetricsHistory(out_dir="", min_interval_s=0.0, engine=eng)
    with pytest.raises(telemetry.AlertRecover) as ei:
        h.sample()
    # The recover signal IS an AlertHalt (background samplers catch it as
    # one) and train()'s wrapper catches the subclass specifically.
    assert isinstance(ei.value, telemetry.AlertHalt)
    telemetry.gauge("selfheal.test.gauge").set(0.0)


# --------------------------------------------------------- coordinator policy

class _FakeProc:
    def __init__(self, code):
        self._code = code

    def wait(self, timeout=None):
        return self._code


def test_coordinator_respawn_policy_budget_and_bookkeeping(monkeypatch):
    from autodist_tpu.coordinator import Coordinator
    monkeypatch.setenv("AUTODIST_WORKER_FAILURE", "respawn")
    monkeypatch.setenv("AUTODIST_RECOVER_MAX", "2")
    respawned = []

    class FakeCluster:
        def remote_exec(self, cmd, address, env=None):
            respawned.append((address, tuple(cmd)))
            return _FakeProc(0)   # the respawned worker exits clean

    coord = Coordinator.__new__(Coordinator)
    coord._cluster = FakeCluster()
    coord._procs = []
    coord._watchdogs = []
    coord._launch_specs = {"10.0.0.2": {"cmd": ["prog"], "env": {"E": "1"},
                                        "respawns": 0}}
    coord.RESPAWN_BACKOFF_S = 0.01
    coord.RESPAWN_BACKOFF_CAP_S = 0.05
    respawns0 = telemetry.counter("recover.respawn").value
    # A nonzero exit respawns the EXACT launch spec instead of killing the
    # chief (the fake proc exits 0, so the chain stops there).
    coord._on_worker_failure("10.0.0.2", 1)
    for w in coord._watchdogs:
        w.join(timeout=5)
    assert respawned == [("10.0.0.2", ("prog",))]
    assert coord._launch_specs["10.0.0.2"]["respawns"] == 1
    assert telemetry.counter("recover.respawn").value == respawns0 + 1
    # Budget exhaustion: _respawn refuses (the caller escalates to halt —
    # os._exit is not testable in-process, the refusal is the decision).
    coord._launch_specs["10.0.0.2"]["respawns"] = 2
    assert coord._respawn("10.0.0.2", 1) is False
    # An address this coordinator never launched refuses too.
    assert coord._respawn("10.9.9.9", 1) is False


def test_coordinator_halt_policy_is_default(monkeypatch):
    from autodist_tpu.coordinator import Coordinator
    monkeypatch.delenv("AUTODIST_WORKER_FAILURE", raising=False)
    assert str(const.ENV.AUTODIST_WORKER_FAILURE.val) == "halt"
    # The overridable seam tests rely on keeps its signature.
    killed = []

    class TestCoordinator(Coordinator):
        def _on_worker_failure(self, address, code):
            killed.append((address, code))
    coord = TestCoordinator.__new__(TestCoordinator)
    coord._on_worker_failure("a", 2)
    assert killed == [("a", 2)]


# ------------------------------------------------------- status + consoles

def test_status_recovery_section_schema_and_console_rendering():
    import importlib.util
    import os as _os
    server, addr = _loopback_stub()
    stub = server._runner
    try:
        recovery.evict(stub.controller, 1, kind="stall", age_s=7.0)
        stub.add_worker(1)    # rejoin
        status = server.status_snapshot()
        rec = status["recovery"]
        assert set(rec) == {"evictions", "rejoins", "rollbacks", "respawns",
                            "counts", "generations"}
        assert rec["counts"]["evicted"] >= 1
        assert rec["counts"]["rejoined"] >= 1
        assert rec["generations"].get(1, 0) >= 1
        # The rename-not-alias contract survives the new section.
        assert "anomalies" not in status
        import json
        json.dumps(status)    # wire-encodable: plain data only
        # adtop renders a recover line; adfleet's row carries the compact
        # fingerprint (both read the same section).
        root = _os.path.join(_os.path.dirname(__file__), _os.pardir, "tools")
        spec = importlib.util.spec_from_file_location(
            "adtop_selfheal", _os.path.join(root, "adtop.py"))
        adtop = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(adtop)
        out = adtop.render(status, addr)
        assert "recover" in out and "evicted" in out and "rejoined" in out
        spec = importlib.util.spec_from_file_location(
            "adfleet_selfheal", _os.path.join(root, "adfleet.py"))
        adfleet = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(adfleet)
        row = adfleet._row(addr, status)
        assert "recov E" in row
    finally:
        server.close()


# ------------------------------------------------------------------ flags

def test_new_flags_registered_and_typed(monkeypatch):
    for name in ("AUTODIST_EVICT_AFTER_S", "AUTODIST_WORKER_FAILURE",
                 "AUTODIST_RECOVER_MAX", "AUTODIST_WIRE_RETRIES",
                 "AUTODIST_WIRE_BACKOFF_S", "AUTODIST_FAULTS"):
        assert name in const.KNOWN_FLAGS
        assert hasattr(const.ENV, name)
    monkeypatch.setenv("AUTODIST_EVICT_AFTER_S", "45.5")
    assert const.ENV.AUTODIST_EVICT_AFTER_S.val == 45.5
    assert recovery.evict_after_s() == 45.5
    monkeypatch.delenv("AUTODIST_EVICT_AFTER_S")
    assert recovery.evict_after_s() is None    # 0/unset = policy off
    monkeypatch.setenv("AUTODIST_RECOVER_MAX", "7")
    assert const.ENV.AUTODIST_RECOVER_MAX.val == 7
    assert recovery.recover_max() == 7
    monkeypatch.setenv("AUTODIST_WIRE_RETRIES", "4")
    assert const.ENV.AUTODIST_WIRE_RETRIES.val == 4
    monkeypatch.setenv("AUTODIST_WIRE_BACKOFF_S", "0.5")
    assert const.ENV.AUTODIST_WIRE_BACKOFF_S.val == 0.5
    assert const.ENV.AUTODIST_WORKER_FAILURE.val == "halt"
    assert const.ENV.AUTODIST_FAULTS.val == ""
