"""HBM memory plane: census, budget, pressure, pre-flight, forensics.

Covers the memory plane end to end (docs/usage/observability.md "Memory
plane") without a single compile probe or training step:

- the tag registry: static and weakref tree claims, re-tag replacement,
  dead-claim pruning, and the ``other`` residual's never-negative clamp;
- budget resolution order (env override vs the warned default on a
  backend with no allocator limit) and the pressure fallback
  (live/budget) that lets a tiny ``AUTODIST_MEM_BUDGET`` inject a squeeze
  on CPU — the degrade paths the plane must survive;
- the shipped ``mem_pressure`` alert rule (pinned verbatim, sustained-not-
  spike semantics) and the squeeze-to-firing path through a real
  ``MetricsHistory`` sample;
- OOM forensics: ``is_oom_error`` recognition, ``record_oom`` writing a
  flight-recorder snapshot whose manifest ``memory`` section names the
  dominant owner;
- the autotuner memory pre-flight: analytic resident model (async / ZeRO /
  accumulation / partition discount), never-fit candidates refused with
  ``pruned: oom`` and ZERO compile probes spent (poisoned-AutoDist pin),
  and ``costmodel.predict``'s ``peak_hbm_bytes``;
- the stable status/snapshot shells and the adtop memory lines.

Pure in-process host tests — no subprocess spawns (GL008-clean), named
test_zmemplane to sort at the tier-1 window's tail (after
test_wire_compress); the whole file budgets well under 15s.
"""

import gc
import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from autodist_tpu import const, telemetry  # noqa: E402
from autodist_tpu.model_spec import ModelSpec  # noqa: E402
from autodist_tpu.resource_spec import ResourceSpec  # noqa: E402
from autodist_tpu.strategy.autotune import (Candidate,  # noqa: E402
                                            TunedPlan,
                                            _predicted_resident_bytes,
                                            _probe_base_costs, autotune,
                                            enumerate_candidates)
from autodist_tpu.telemetry import alerts  # noqa: E402
from autodist_tpu.telemetry import costmodel  # noqa: E402
from autodist_tpu.telemetry import history as _history  # noqa: E402
from autodist_tpu.telemetry import memplane  # noqa: E402
from autodist_tpu.telemetry import metrics as _metrics  # noqa: E402
from autodist_tpu.telemetry import recorder  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_state():
    """Leave process-global telemetry/memplane/recorder/alerts as found."""
    telemetry.disable()
    telemetry.clear()
    memplane.reset()
    recorder.set_recorder(None)
    alerts.set_engine(None)
    yield
    telemetry.disable()
    telemetry.clear()
    memplane.reset()
    recorder.set_recorder(None)
    alerts.set_engine(None)


# ------------------------------------------------------------------ fixtures

def _loss(p, b):
    return jnp.mean((b["y"] - b["x"] @ p["w"]) ** 2)


def _params():
    return {"w": np.random.RandomState(0).randn(8, 4).astype(np.float32)}


def _batch(rows=16):
    rng = np.random.RandomState(1)
    return {"x": rng.randn(rows, 8).astype(np.float32),
            "y": rng.randn(rows, 4).astype(np.float32)}


# --------------------------------------------------------------------- flags

def test_new_flags_registered_and_typed(monkeypatch):
    for flag in ("AUTODIST_MEM_BUDGET", "AUTODIST_MEM_PRESSURE"):
        assert flag in const.KNOWN_FLAGS and const.KNOWN_FLAGS[flag]
        assert hasattr(const.ENV, flag)
    monkeypatch.setenv("AUTODIST_MEM_BUDGET", "123456")
    assert const.ENV.AUTODIST_MEM_BUDGET.val == 123456
    monkeypatch.setenv("AUTODIST_MEM_PRESSURE", "0.5")
    assert const.ENV.AUTODIST_MEM_PRESSURE.val == 0.5
    assert memplane.pressure_threshold() == 0.5
    monkeypatch.delenv("AUTODIST_MEM_PRESSURE")
    assert memplane.pressure_threshold() == 0.92


# ------------------------------------------------------------- tag registry

def test_tag_census_attribute_and_residual_clamp():
    memplane.tag("kv_pages", 1000)                     # static bytes claim
    arr = jnp.ones((128,), jnp.float32) * 2.0          # 512 device bytes
    tree = {"w": arr}
    memplane.tag("params", tree)                       # weakref tree claim
    counts = memplane.census()
    assert counts["kv_pages"] == 1000
    assert counts["params"] == 512
    owned = memplane.attribute(2000)
    assert set(owned) == set(memplane.OWNERS) | {"other"}
    assert owned["params"] == 512 and owned["kv_pages"] == 1000
    assert owned["opt_state"] == 0                     # unclaimed -> 0, stable
    assert owned["other"] == 2000 - 1512
    # The residual is a leak detector: claims overshooting the live gauge
    # must clamp to 0, never report a negative leak.
    assert memplane.attribute(100)["other"] == 0
    # Re-tag replaces; untag drops (idempotent).
    memplane.tag("kv_pages", 777)
    assert memplane.census()["kv_pages"] == 777
    memplane.untag("kv_pages")
    memplane.untag("kv_pages")
    assert "kv_pages" not in memplane.census()
    del tree, arr


def test_weakref_claim_dies_with_the_tree():
    arr = jnp.arange(256, dtype=jnp.float32) + 1.0
    memplane.tag("prefetch", {"batch": arr}, key="feed.0")
    assert memplane.census()["prefetch"] == 1024
    del arr
    gc.collect()
    assert "prefetch" not in memplane.census()
    # Keyed claims scope concurrent claimants of one owner.
    memplane.tag("kv_pages", 100, key="pool.a")
    memplane.tag("kv_pages", 200, key="pool.b")
    assert memplane.census()["kv_pages"] == 300


# ------------------------------------------------------- budget and pressure

def test_device_budget_env_and_default_sources(monkeypatch):
    # CPU reports no allocator limit, so the env override wins when set...
    monkeypatch.setenv("AUTODIST_MEM_BUDGET", "123456789")
    budget, source = memplane.device_budget()
    assert (budget, source) == (123456789, "env")
    snap = _metrics.snapshot()
    assert snap["mem.budget_bytes"] == 123456789
    assert snap["mem.budget_source"] == 1.0
    # ...and the warned 8 GiB default backstops when nothing answers.
    monkeypatch.delenv("AUTODIST_MEM_BUDGET")
    budget, source = memplane.device_budget()
    assert (budget, source) == (memplane.DEFAULT_BUDGET_BYTES, "default")
    assert _metrics.snapshot()["mem.budget_source"] == 0.0


def test_pressure_fallback_drives_kv_holdback(monkeypatch):
    # No allocator stats on CPU -> pressure degrades to live/budget, so a
    # tiny AUTODIST_MEM_BUDGET injects a squeeze the whole plane reacts to.
    monkeypatch.setenv("AUTODIST_MEM_BUDGET", "1")
    keep = jnp.ones((64,), jnp.float32) + 0.0   # some live bytes to measure
    assert memplane.current_pressure(max_age_s=0.0) > 0.92
    assert memplane.kv_admission_holdback(100) == 25   # 25% of the pool
    assert memplane.kv_admission_holdback(1) == 1      # max(1, ...) floor
    assert memplane.kv_admission_holdback(0) == 0      # empty pool: inert
    # Below the threshold the holdback vanishes — admission is unchanged.
    monkeypatch.setenv("AUTODIST_MEM_BUDGET", str(1 << 50))
    assert memplane.current_pressure(max_age_s=0.0) < 0.92
    assert memplane.kv_admission_holdback(100) == 0
    del keep


# ------------------------------------------------------------ degrade paths

def test_sample_device_memory_degrades_clean_on_cpu():
    """CPU reports no allocator stats and opt_state=None skips the
    opt-state gauge — the attributed sample must still book the census
    and pressure, and never raise."""
    # Earlier suites may already have booked train.opt_state_bytes in the
    # process-global registry — pin "this call left it untouched", not
    # global absence.
    before = _metrics.snapshot().get("train.opt_state_bytes")
    arr = jnp.ones((64,), jnp.float32) * 3.0
    memplane.tag("params", {"w": arr})
    wrote = telemetry.sample_device_memory()           # opt_state=None
    assert wrote > 0
    snap = _metrics.snapshot()
    assert snap.get("train.opt_state_bytes") == before
    assert snap["device.live_bytes"] >= 256
    for owner in memplane.OWNERS + ("other",):
        assert f"mem.owned.{owner}" in snap
    assert snap["mem.owned.params"] == 256
    assert snap["mem.owned.other"] >= 0
    assert "mem.pressure" in snap
    del arr


def test_memory_snapshot_shell_is_stable_when_unarmed():
    assert memplane.memory_snapshot() == {
        "owned": {}, "live_bytes": 0, "pressure": 0.0, "budget_bytes": 0,
        "budget_source": "", "devices": {}}


def test_memory_snapshot_and_section_when_armed():
    arr = jnp.ones((512,), jnp.float32) + 0.0
    memplane.tag("params", {"w": arr})                 # claims arm the plane
    snap = memplane.memory_snapshot()
    assert snap["live_bytes"] >= 2048
    assert snap["owned"]["params"] == 2048
    assert snap["budget_source"] in ("default", "env", "measured")
    # The autopsy's opening line (test_zmemplane_hbm.py): what the HBM account
    # predicted a chip holds while a step runs, from the gauge a boundary
    # booked, against the allocator's own peak (the live bytes where, as
    # here, the backend keeps no statistics).
    telemetry.gauge("train.hbm.predicted_bytes").set(5000)
    section = memplane.memory_section()
    for key in ("programs", "history", "predicted_peak_bytes",
                "live_peak_bytes", "peak_delta_bytes"):
        assert key in section
    live = section["live_bytes"]
    assert live >= 2048
    assert (section["predicted_peak_bytes"], section["live_peak_bytes"],
            section["peak_delta_bytes"]) == (5000, live, live - 5000)
    json.dumps(section)                                # wire/manifest-encodable
    del arr


def test_snapshot_ring_states_feed_the_census():
    from autodist_tpu.parallel.recovery import SnapshotRing
    ring = SnapshotRing(keep=2)
    a = jnp.ones((32,), jnp.float32) * 1.0
    b = jnp.ones((32,), jnp.float32) * 2.0
    ring.push(1, {"w": a})
    ring.push(2, {"w": b})
    states = ring.states()
    assert len(states) == 2                            # oldest first, public
    memplane.tag("snapshots", states)
    assert memplane.census()["snapshots"] == 256       # both retained states


# -------------------------------------------------------------- alert rule

def test_mem_pressure_rule_shipped_verbatim():
    entry = next(r for r in alerts.DEFAULT_RULES if r["name"] == "mem_pressure")
    assert entry == {"name": "mem_pressure", "kind": "threshold",
                     "metric": "mem.pressure", "op": ">", "value": 0.92,
                     "for_s": 30.0}


class _FakeHistory:
    """Duck-typed history ring with synthetic timestamps — lets the 30s
    sustain window be tested without 30s of wall clock."""

    def __init__(self, rows):
        self._rows = rows

    def latest(self):
        return self._rows[-1] if self._rows else None

    def samples(self):
        return list(self._rows)

    def window(self, seconds, now=None):
        cut = self._rows[-1]["t_mono_s"] - seconds
        return [r for r in self._rows if r["t_mono_s"] >= cut]


def test_mem_pressure_rule_fires_sustained_not_spike():
    rule = alerts.AlertRule.from_dict(
        next(r for r in alerts.DEFAULT_RULES if r["name"] == "mem_pressure"))

    def row(t, value):
        return {"t_mono_s": t, "metrics": {"mem.pressure": value}}

    # One fresh spike proves nothing about duration: no firing.
    assert rule.evaluate(_FakeHistory([row(1000.0, 0.99)])) is None
    # 40s of sustained pressure: fires with value and bound.
    sustained = _FakeHistory([row(1000.0 + 5 * i, 0.97) for i in range(9)])
    detail = rule.evaluate(sustained)
    assert detail == {"value": 0.97, "bound": 0.92}
    # A recovery inside the window resets the incident.
    dipped = _FakeHistory([row(1000.0 + 5 * i, 0.97) for i in range(8)]
                          + [row(1038.0, 0.5), row(1040.0, 0.97)])
    assert rule.evaluate(dipped) is None


def test_injected_squeeze_fires_through_history_sample(monkeypatch):
    """The e2e squeeze pin: tiny budget -> mem.pressure books past the
    threshold on the attributed sample -> the rule fires on the very next
    history tick -> forensics name the dominant owner."""
    monkeypatch.setenv("AUTODIST_MEM_BUDGET", "1")
    arr = jnp.ones((1024,), jnp.float32) * 2.0
    memplane.tag("params", {"w": arr})
    eng = alerts.AlertEngine(rules=[alerts.AlertRule(
        name="mem_pressure", kind="threshold", metric="mem.pressure",
        op=">", value=0.92)], action="warn")   # for_s=0: fire on first tick
    alerts.set_engine(eng)
    telemetry.sample_device_memory()                   # books mem.pressure
    h = _history.MetricsHistory(out_dir="", min_interval_s=0.0, engine=eng)
    h.sample()
    assert [a["rule"] for a in eng.active()] == ["mem_pressure"]
    section = memplane.memory_section()
    dominant = max(memplane.OWNERS, key=lambda o: section["owned"][o])
    assert dominant == "params"
    del arr


# ------------------------------------------------------------ OOM forensics

def test_is_oom_error_recognition():
    XlaRuntimeError = type("XlaRuntimeError", (RuntimeError,), {})
    assert memplane.is_oom_error(XlaRuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating 123456 bytes"))
    assert memplane.is_oom_error(RuntimeError(
        "RESOURCE_EXHAUSTED: while allocating buffer"))
    assert not memplane.is_oom_error(ValueError("shape mismatch (8,4)"))
    assert not memplane.is_oom_error(XlaRuntimeError("INVALID_ARGUMENT"))


def test_record_oom_writes_memory_autopsy(tmp_path):
    recorder.set_recorder(recorder.FlightRecorder(
        str(tmp_path / "fr"), keep=2, min_interval_s=0.0))
    arr = jnp.ones((1024,), jnp.float32) + 0.0
    memplane.tag("params", {"w": arr})
    memplane.tag("kv_pages", 64)
    XlaRuntimeError = type("XlaRuntimeError", (RuntimeError,), {})
    memplane.record_oom("train_step", XlaRuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating 4096 bytes"))
    assert _metrics.snapshot()["mem.oom"] == 1
    snaps = recorder.get_recorder().snapshots()
    assert len(snaps) == 1 and "oom.train_step" in snaps[0]
    manifest = json.load(open(os.path.join(snaps[0], "manifest.json")))
    owned = manifest["memory"]["owned"]
    assert owned["params"] == 4096 and owned["kv_pages"] == 64
    assert max(memplane.OWNERS, key=lambda o: owned[o]) == "params"
    # The prediction is the HBM account's last boundary's, the live peak the
    # allocator's (the live bytes on this backend), the delta theirs.
    booked = telemetry.registry().get("train.hbm.predicted_bytes")
    predicted = None if booked is None else int(booked.value)
    live = manifest["memory"]["live_bytes"]
    assert manifest["memory"]["predicted_peak_bytes"] == predicted
    assert manifest["memory"]["live_peak_bytes"] == live >= 4096
    assert manifest["memory"]["peak_delta_bytes"] == (
        None if predicted is None else live - predicted)
    del arr


# ------------------------------------------------------- autotune pre-flight

def test_predicted_resident_bytes_analytic_model():
    sync = Candidate({"name": "AllReduce"})
    assert _predicted_resident_bytes(sync, 100, 50, 8) == 150
    zero = Candidate({"name": "AllReduce"}, zero=1)
    assert _predicted_resident_bytes(zero, 100, 50, 8) == 100 + 50 // 8
    accum = Candidate({"name": "AllReduce"}, accumulation_steps=2)
    assert _predicted_resident_bytes(accum, 100, 50, 8) == 250
    async_c = Candidate({"name": "PS", "kwargs": {"sync": False}},
                        asynchronous=True)
    assert _predicted_resident_bytes(async_c, 100, 50, 8) == 200
    # No exact opt-state footprint: the Adam-shaped 2x-params fallback.
    assert _predicted_resident_bytes(sync, 100, None, 8) == 300


def test_preflight_refuses_never_fit_with_zero_compile_probes(monkeypatch):
    """The e2e oom pin: with a budget below even the model's resident
    params, EVERY candidate is refused before stage 1 and not one compile
    probe is spent (a poisoned AutoDist would raise if one were)."""
    monkeypatch.setenv("AUTODIST_MEM_BUDGET", "64")    # dense params are 128B
    calls = []

    class _PoisonAutoDist:
        def __init__(self, *a, **kw):
            calls.append(a)
            raise AssertionError(
                "compile probe spent on a pre-flight-refused candidate")

    monkeypatch.setattr("autodist_tpu.autodist.AutoDist", _PoisonAutoDist)
    spec = ModelSpec(_params())
    cands = enumerate_candidates(spec, ResourceSpec(None), optax.sgd(0.1),
                                 unrolls=(1, 2), accums=(1,))
    assert cands
    for c in cands:
        assert c.resident_bytes is not None and c.resident_bytes > 64
        assert c.pruned and c.pruned.startswith("oom:")
    base_costs = _probe_base_costs(cands, _loss, _params(), optax.sgd(0.1),
                                   _batch(), ResourceSpec(None), None, False)
    assert base_costs == {} and calls == []
    # The refusal reason renders in the explain table...
    table = TunedPlan(builder_spec={"name": "AllReduce"}, candidates=cands,
                      enumerated=len(cands)).explain()
    assert "pruned: oom: predicted resident" in table
    # ...and a full search against the same budget refuses up front,
    # naming the oom reasons — still zero probes (the poison is live).
    with pytest.raises(RuntimeError, match="oom: predicted resident"):
        autotune(_loss, _params(), optax.sgd(0.1), _batch(),
                 plan_cache="", unrolls=(1,), top_k=1)
    assert calls == []


def test_preflight_partition_discount_spares_sharded_plans(monkeypatch):
    """A 64 MiB param over 8 devices: the dense plans' resident state
    busts a 16 MiB budget, but the partitioned builders keep that param
    sharded 1/n_dev — refusing them on the DENSE footprint would prune
    exactly the plans that fit."""
    monkeypatch.setenv("AUTODIST_MEM_BUDGET", str(16 << 20))
    spec = ModelSpec({"big": np.zeros((4096, 4096), np.float32)})
    cands = enumerate_candidates(spec, ResourceSpec(None), optax.sgd(0.1),
                                 unrolls=(1,), accums=(1,))
    by_name = {}
    for c in cands:
        by_name.setdefault(c.builder_spec["name"], []).append(c)
    assert all(c.pruned and c.pruned.startswith("oom:")
               for c in by_name["AllReduce"])
    assert any(not c.pruned for c in by_name["PartitionedAR"])


def test_costmodel_predict_carries_peak_hbm():
    calib = costmodel.Calibration(flops_per_s=1e12, bytes_per_s=1e11,
                                  host_s_per_dispatch=1e-3)
    rec = {"flops": 1e9, "bytes_accessed": 1e6, "steps": 1, "dispatches": 1,
           "temp_bytes": 4096}
    pred = costmodel.predict(rec, calib, resident_bytes=1000.0)
    assert pred["peak_hbm_bytes"] == 1000 + 4096
    # No temp ledger: argument + output bytes stand in for the transient.
    rec2 = {"flops": 1e9, "argument_bytes": 10, "output_bytes": 20}
    assert costmodel.predict(rec2, calib)["peak_hbm_bytes"] == 30
    # Neither resident nor any memory ledger: honestly None, not 0.
    assert costmodel.predict({"flops": 1e9}, calib)["peak_hbm_bytes"] is None


# ------------------------------------------------------------------ console

def test_adtop_memory_lines_render():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "adtop", os.path.join(os.path.dirname(__file__), os.pardir,
                              "tools", "adtop.py"))
    adtop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(adtop)
    status = {"memory": {
        "owned": {"params": 4096, "opt_state": 8192, "kv_pages": 0,
                  "prefetch": 0, "snapshots": 0, "other": 100},
        "live_bytes": 12388, "pressure": 0.9412,
        "budget_bytes": 8 << 30, "budget_source": "default", "devices": {}}}
    lines = adtop._memory_lines(status)
    head = lines[0]
    assert "mem" in head and "pressure 0.94" in head
    assert any("opt_state" in ln for ln in lines[1:])
    # The unarmed shell renders nothing — no dead rows on healthy consoles.
    assert adtop._memory_lines({"memory": {
        "owned": {}, "live_bytes": 0, "pressure": 0.0, "budget_bytes": 0,
        "budget_source": "", "devices": {}}}) == []
    assert adtop._memory_lines({}) == []
