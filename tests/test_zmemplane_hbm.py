"""The HBM account of the memory plane (docs/usage/observability.md "Memory
plane"): what a chip holds at a fenced log boundary, whose it is, what a
running step adds, and what is left.

The CPU backend keeps no allocator statistics, so every reading comes from a
stub of ``memplane.device_stats`` (the one place the plane calls
``memory_stats()``): the arithmetic of the identity, a sharded tree counted
by a device's shards, one walk of the state a boundary, the account's life
(opened at ``train()``'s first pull, never with telemetry off, no thread),
the step's own account (once a signature, profiling plane off, no lowering,
nothing asked of the backend), what the step's checkpointed layers keep as a
chip's share, and ``memory_section``'s keys.

In-process host tests on the 8-device mesh, beside test_zmemplane at the
tier-1 window's tail; one tiny linear model, a few seconds in all.
"""

import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from autodist_tpu import AutoDist, telemetry  # noqa: E402
from autodist_tpu.models.common import keeping  # noqa: E402
from autodist_tpu.runner import DistributedRunner  # noqa: E402
from autodist_tpu.strategy import AllReduce  # noqa: E402
from autodist_tpu.telemetry import export  # noqa: E402
from autodist_tpu.telemetry import memplane  # noqa: E402
from autodist_tpu.telemetry import metrics  # noqa: E402
from autodist_tpu.telemetry import profiling  # noqa: E402
from autodist_tpu.training import train  # noqa: E402
from autodist_tpu.utils import compile_cache  # noqa: E402

GIB = 2 ** 30


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    """A registry of this test's own, no span, no claim, no open account."""
    telemetry.disable()
    telemetry.clear()
    memplane.reset()
    profiling.reset()
    monkeypatch.setattr(metrics, "_REGISTRY", metrics.Registry())
    yield
    telemetry.disable()
    telemetry.clear()
    memplane.reset()
    profiling.reset()


def _value(name):
    instrument = telemetry.registry().get(name)
    return None if instrument is None else instrument.value


class _Allocator:
    """A stub of the allocator behind ``memplane.device_stats``: every device
    reads ``used`` bytes in use, ``limit`` and ``peak``; counts the
    readings."""

    def __init__(self, used=1000, limit=10000, peak=2000):
        self.used, self.limit, self.peak = used, limit, peak
        self.reads = 0

    def __call__(self, device):
        self.reads += 1
        return {"bytes_in_use": self.used, "bytes_limit": self.limit,
                "peak_bytes_in_use": self.peak}


def _loss(p, b):
    return jnp.mean((b["y"] - b["x"] @ p["w"]) ** 2)


def _params():
    return {"w": np.random.RandomState(0).randn(8, 4).astype(np.float32)}


def _batch(i=0, rows=16):
    rng = np.random.RandomState(i)
    return {"x": rng.randn(rows, 8).astype(np.float32),
            "y": rng.randn(rows, 4).astype(np.float32)}


def _runner(loss=_loss):
    return AutoDist(strategy_builder=AllReduce()).create_distributed_session(
        loss, _params(), optax.adam(1e-2), example_batch=_batch())


# ------------------------------------------------------------ the identity

@pytest.mark.parametrize("resident, temp, predicted, headroom", [
    # A chip that holds little beside the state: the batch and the step's
    # temporaries on top of what the boundary read.
    (8 * GIB, 4 * GIB, 12 * GIB + 64, 4 * GIB - 64),
    # No temporaries: the step adds its batch alone.
    (8 * GIB, 0, 8 * GIB + 64, 8 * GIB - 64),
    # More than the chip has (a count that runs all the same says the
    # allocator books less than the compiler counts): the room is negative.
    (13 * GIB, 4 * GIB, 17 * GIB + 64, -(GIB + 64)),
])
def test_account_arithmetic(resident, temp, predicted, headroom):
    account = memplane.hbm_account(
        resident=resident, state=3 * GIB, limit=16 * GIB,
        argument=3 * GIB + 64, temp=temp, output=3 * GIB, alias=3 * GIB)
    # resident + the batch (argument - state) + temp + output - alias
    assert account == {"predicted_bytes": predicted,
                       "headroom_bytes": headroom}


# ------------------------------------------------- a chip's bytes, any mesh

def _mesh_tree():
    mesh = Mesh(np.array(jax.devices()), ("data",))
    shared = jax.device_put(np.zeros((64, 16), np.float32),
                            NamedSharding(mesh, P("data")))
    whole = jax.device_put(np.zeros((32,), np.float32),
                           NamedSharding(mesh, P()))
    return {"shared": shared, "whole": whole}


def test_sharded_tree_is_counted_by_a_devices_shards_not_by_nbytes():
    tree = _mesh_tree()
    assert sum(leaf.nbytes for leaf in tree.values()) == 4096 + 128
    per_dev, host = export.device_bytes(tree)
    assert host == 0 and set(per_dev) == {d.id for d in jax.devices()}
    assert set(per_dev.values()) == {4096 // 8 + 128}   # an eighth + a copy
    assert telemetry.opt_state_bytes(tree) == 4096 // 8 + 128
    assert telemetry.opt_state_bytes({"host": np.zeros(5, np.float32)}) == 20


def test_census_and_other_are_a_chips_bytes_on_a_mesh():
    tree = _mesh_tree()
    memplane.tag("params", tree)
    assert memplane.census()["params"] == 4096 // 8 + 128
    telemetry.sample_device_memory(opt_state=tree)
    assert _value("mem.owned.params") == 4096 // 8 + 128
    assert _value("train.opt_state_bytes") == 4096 // 8 + 128
    # `other` is what the fullest chip holds beside the claims (whatever
    # else this process keeps alive), never the mesh's eight copies of them.
    chip_live = telemetry.opt_state_bytes(jax.live_arrays())
    assert _value("mem.owned.other") == chip_live - (4096 // 8 + 128)
    # One unit: the live gauge and the status section's key are a chip's too.
    assert _value("device.live_bytes") == chip_live
    assert memplane.memory_snapshot()["live_bytes"] == chip_live


def test_boundary_books_the_fullest_chips_readings():
    held, _ = export.device_bytes(_mesh_tree())
    ids = [d.id for d in jax.devices()]
    stats = {i: {"bytes_in_use": 5000, "bytes_limit": 16000,
                 "peak_bytes_in_use": 9000} for i in ids}
    stats[ids[3]] = {"bytes_in_use": 7000, "bytes_limit": 16000,
                     "peak_bytes_in_use": 8000}
    assert memplane.book_hbm_boundary(stats, held) == 5
    assert _value("train.hbm.resident_bytes") == 7000
    assert _value("train.hbm.limit_bytes") == 16000
    assert _value("train.hbm.state_bytes") == 4096 // 8 + 128
    assert _value("train.hbm.unowned_bytes") == 7000 - (4096 // 8 + 128)
    assert _value("train.hbm.allocator_peak_bytes") == 9000
    # No open account, no step account: no rise, no identity.
    for name in ("allocator_peak_rise_bytes", "predicted_bytes",
                 "headroom_bytes"):
        assert _value(f"train.hbm.{name}") is None
    assert memplane.book_hbm_boundary({}, held) == 0    # CPU: nothing to read


def test_a_boundary_walks_the_state_once(monkeypatch):
    """The census's claims, ``train.opt_state_bytes`` and
    ``train.hbm.state_bytes`` are one walk's; the only other visit a leaf
    gets is as one of the process's live arrays. A shard's bytes are looked
    up, not computed, from the second boundary on."""
    monkeypatch.setattr(memplane, "device_stats", _Allocator())
    runner = _runner()
    state = runner.init(_params())
    leaves = jax.tree_util.tree_leaves((state.params, state.opt_state))
    visits = {id(leaf): 0 for leaf in leaves}
    counted = export.leaf_device_bytes

    def counting(leaf):
        if id(leaf) in visits:
            visits[id(leaf)] += 1
        return counted(leaf)

    monkeypatch.setattr(export, "leaf_device_bytes", counting)
    telemetry.sample_device_memory(state=state)
    assert set(visits.values()) == {2}
    state_bytes = 3 * 8 * 4 * 4
    assert _value("mem.owned.params") == 8 * 4 * 4
    assert state_bytes - 8 * 4 * 4 <= _value("mem.owned.opt_state") \
        == _value("train.opt_state_bytes") <= state_bytes
    assert state_bytes + 4 <= _value("train.hbm.state_bytes") \
        <= state_bytes + 16
    misses = export._shard_bytes.cache_info().misses
    telemetry.sample_device_memory(state=state)
    assert export._shard_bytes.cache_info().misses == misses


# -------------------------------------------------------- the account's life

def test_account_opens_at_the_first_pull_and_starts_no_thread(monkeypatch):
    allocator = _Allocator(peak=2000)
    monkeypatch.setattr(memplane, "device_stats", allocator)
    runner = _runner()
    threads = []

    def batches(i):
        threads.append(threading.active_count())
        if i == 7:
            allocator.peak = 2600         # the window raises the peak
        return _batch(i)

    telemetry.enable()
    before = threading.active_count()
    rises = []
    train(runner, _params(), batches, steps=12, log_every=5,
          on_metrics=lambda *_: rises.append(
              _value("train.hbm.allocator_peak_rise_bytes")))
    assert rises == [0, 600] and set(threads) == {before}
    assert _value("train.hbm.allocator_peak_bytes") == 2600
    assert _value("train.hbm.resident_bytes") == 1000
    assert not [s for s in telemetry.snapshot_spans()
                if s[0].startswith("train.hbm.")]       # gauges, no span


def test_no_account_where_no_device_keeps_statistics():
    runner = _runner()
    telemetry.enable()                    # the CPU backend as it is
    train(runner, _params(), _batch, steps=4, log_every=2)
    assert not [name for name in telemetry.snapshot()
                if name.startswith("train.hbm.")]
    assert _value("step.hbm.temp_bytes") is not None    # the compiler's count
    section = memplane.memory_section()
    assert (section["predicted_peak_bytes"], section["live_peak_bytes"],
            section["peak_delta_bytes"]) == (None, section["live_bytes"], None)


def test_telemetry_off_runs_nothing_of_the_account(monkeypatch):
    allocator = _Allocator()
    monkeypatch.setattr(memplane, "device_stats", allocator)
    analyses = []
    monkeypatch.setattr(DistributedRunner, "_compiled_memory",
                        lambda self, compiled: analyses.append(1) or {})
    runner = _runner()
    started = []
    monkeypatch.setattr(threading.Thread, "start",
                        lambda self: started.append(self.name))
    assert not telemetry.enabled()
    state = train(runner, _params(), _batch, steps=8, log_every=3)
    runner.run(state, _batch(9))
    assert allocator.reads == 0 and analyses == [] and started == []
    assert not [name for name in telemetry.snapshot()
                if name.startswith(("train.hbm.", "step.hbm."))]


def test_section_of_a_process_without_a_train_loop(monkeypatch):
    """A serving process's autopsy: no prediction, and the allocator's own
    peak (its level where the backend keeps no peak) as the live one."""
    allocator = _Allocator(used=3000, peak=4500)
    monkeypatch.setattr(memplane, "device_stats", allocator)
    memplane.tag("kv_pages", 2048)
    section = memplane.memory_section()
    assert section["live_peak_bytes"] == 4500
    assert section["devices"]["d0"]["peak_bytes_in_use"] == 4500
    assert (section["predicted_peak_bytes"], section["peak_delta_bytes"]) \
        == (None, None)
    monkeypatch.setattr(
        memplane, "device_stats",
        lambda d: {"bytes_in_use": 3000, "bytes_limit": 10000})
    section = memplane.memory_section()
    assert section["live_peak_bytes"] == 3000
    assert "peak_bytes_in_use" not in section["devices"]["d0"]


# ------------------------------------------------- the step's own account

class _BackendRequests:
    """Programs JAX asks its backend for while used as a context manager."""

    def __init__(self):
        self.count = 0

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


def test_step_account_once_a_signature_without_lowering_or_backend():
    compile_cache.configure()             # the jit-stage listeners, as set-up
    runner = _runner()
    state = runner.init(_params())
    state, _ = runner.run(state, _batch())          # traced, lowered, compiled
    lowerings = _value("jit.step.lowerings")
    assert lowerings >= 1
    assert _value("step.hbm.account_s") is None     # telemetry was off
    telemetry.enable()
    assert not profiling.active()                   # the profiling plane is off
    with _BackendRequests() as requests:
        for i in range(3):
            state, _ = runner.run(state, _batch(i))
    assert requests.count == 0
    assert _value("jit.step.lowerings") == lowerings
    booked = _value("step.hbm.account_s")
    assert booked > 0
    memory = {name: _value(f"step.hbm.{name}") for name in
              ("argument_bytes", "temp_bytes", "output_bytes", "alias_bytes",
               "code_bytes")}
    assert None not in memory.values()
    # params, adam's two moments and the step donated: the new state lives in
    # the old one's buffers, the batch beside it.
    state_bytes = 3 * 8 * 4 * 4
    assert memory["alias_bytes"] >= state_bytes
    assert memory["argument_bytes"] >= state_bytes + 16 * 12 * 4 // 8
    (record,) = profiling.program_costs().values()
    assert record.alias_bytes == memory["alias_bytes"]
    assert record.temp_bytes == memory["temp_bytes"]
    assert record.flops is None                     # no cost probe was paid
    assert record.to_dict()["alias_bytes"] == memory["alias_bytes"]
    # A new signature (another batch size) is a new program: a second account.
    state, _ = runner.run(state, _batch(rows=32))
    assert _value("step.hbm.account_s") > booked
    assert len(profiling.program_costs()) == 2


def test_identity_is_stated_at_the_boundary(monkeypatch):
    allocator = _Allocator(used=5000, limit=20000, peak=6000)
    monkeypatch.setattr(memplane, "device_stats", allocator)
    runner = _runner()
    telemetry.enable()
    train(runner, _params(), _batch, steps=7, log_every=3)
    step = {name: _value(f"step.hbm.{name}") for name in
            ("argument_bytes", "temp_bytes", "output_bytes", "alias_bytes")}
    held = _value("train.hbm.state_bytes")
    # The replicated state: params and adam's moments, the step, counters.
    assert 3 * 8 * 4 * 4 + 4 <= held <= 3 * 8 * 4 * 4 + 16
    predicted = (5000 + step["argument_bytes"] - held + step["temp_bytes"]
                 + step["output_bytes"] - step["alias_bytes"])
    assert predicted > 5000
    assert _value("train.hbm.predicted_bytes") == predicted
    assert _value("train.hbm.headroom_bytes") == 20000 - predicted
    # The autopsy's opening line: the prediction, the allocator's own peak.
    section = memplane.memory_section()
    assert section["predicted_peak_bytes"] == predicted
    assert section["live_peak_bytes"] == 6000
    assert section["peak_delta_bytes"] == 6000 - predicted
    (program,) = section["programs"].values()
    assert program["alias_bytes"] == step["alias_bytes"]


def _kept_loss(p, b):
    from jax.ad_checkpoint import checkpoint_name
    layer = jax.checkpoint(
        lambda x: jnp.tanh(checkpoint_name(x @ p["w"], "h")) + 1.0,
        policy=keeping(["h"]))
    return jnp.mean((b["y"] - layer(b["x"])) ** 2)


def test_kept_bytes_are_a_chips_share_of_this_steps_layers():
    telemetry.gauge("remat.kept_bytes").set(123456)   # an earlier model's
    runner = _runner(_kept_loss)
    state = runner.init(_params())
    state, _ = runner.run(state, _batch())            # traced, telemetry off
    # One [16, 4] float32 value, of the global batch as the trace sees it.
    assert _value("remat.kept_bytes") == 16 * 4 * 4
    assert _value("step.hbm.kept_bytes") is None
    telemetry.enable()
    runner.run(state, _batch(1))          # booked with the step's account
    assert _value("step.hbm.kept_bytes") == 16 * 4 * 4 // 8
    assert _value("step.hbm.temp_bytes") is not None


def test_no_kept_bytes_where_no_layer_is_checkpointed():
    telemetry.gauge("remat.kept_bytes").set(123456)   # an earlier model's
    runner = _runner()
    telemetry.enable()
    runner.run(runner.init(_params()), _batch())
    assert _value("step.hbm.account_s") > 0
    assert _value("step.hbm.kept_bytes") is None


@pytest.mark.parametrize("compressor, shards", [
    ("NoneCompressor", 8),        # implicit: the trace sees the global batch
    ("HorovodCompressor", 1),     # explicit: a data shard a trace
])
def test_batch_trace_shards_follow_the_lowering(compressor, shards):
    from autodist_tpu.parallel import synchronization
    runner = AutoDist(strategy_builder=AllReduce(
        compressor=compressor)).create_distributed_session(
        _kept_loss, _params(), optax.adam(1e-2), example_batch=_batch())
    assert synchronization.batch_trace_shards(runner.plan, runner.mesh) \
        == shards
    state = runner.init(_params())
    telemetry.enable()
    runner.run(state, _batch())
    # The shard's [2, 4] value as it is, the global [16, 4] over its shards.
    assert _value("step.hbm.kept_bytes") == 16 * 4 * 4 // 8
