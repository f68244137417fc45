"""State stored as shares over the data axis (``strategy.FullySharded``), on 4
of the 8 host devices: the same loss and the same parameters after three AdamW
steps as the single-device step; every large leaf, its gradient's landing
place and both Adam moments a quarter a device, small leaves whole; the
compiled step gathers weights; the gauges' bytes equal the plan's arithmetic;
``per_device`` hands a kernel a stored leaf as its share and gathers it in the
body, so the gradient leaves reduce-scattered; a caller that lays its
parameters out with the strategy's rule is not moved by ``init``.

Named ``test_dp_*`` so it sorts in-window (``test_dp_zero_update``'s note).
The compiled text of the chip's own compiler is read in
``tests/test_chip_compile.py``: the CPU's keeps every reduction an all-reduce
and slices inside its fusions."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from autodist_tpu import ResourceSpec, telemetry
from autodist_tpu.model_spec import ModelSpec
from autodist_tpu.parallel import mesh as mesh_lib
from autodist_tpu.parallel.plan import ShardingPlan
from autodist_tpu.runner import DistributedRunner
from autodist_tpu.strategy import AllReduce, FullySharded
from autodist_tpu.strategy.partition_utils import (MIN_SHARDED_SIZE,
                                                   data_shard_axis)

D, BATCH = 512, 16                    # leaves of MIN_SHARDED_SIZE and twice it


def _loss(p, b):
    h = mesh_lib.constrain_batch(b["x"])
    h = mesh_lib.constrain_batch(jnp.tanh(h @ p["w1"] + p["b1"]))
    return jnp.mean((b["y"] - h @ p["w2"]) ** 2)


def _params():
    rng = np.random.RandomState(7)
    return {"w1": rng.randn(D, 2 * D).astype(np.float32) * D ** -0.5,
            "b1": np.zeros((2 * D,), np.float32),
            "w2": rng.randn(2 * D, D).astype(np.float32) * D ** -0.5}


def _batch(i):
    rng = np.random.RandomState(100 + i)
    return {"x": rng.randn(BATCH, D).astype(np.float32),
            "y": rng.randn(BATCH, D).astype(np.float32)}


def _runner(builder, chips):
    spec = ResourceSpec(resource_info={
        "nodes": [{"address": "localhost", "tpus": chips, "chief": True}],
        "mesh": {"data": chips}})
    model_spec = ModelSpec.from_loss_fn(_loss, _params(), _batch(0))
    strategy = builder.build(model_spec, spec)
    mesh = mesh_lib.build_mesh(axes={"data": chips},
                               devices=jax.devices()[:chips])
    return DistributedRunner(strategy, model_spec, _loss, optax.adamw(1e-2),
                             mesh=mesh,
                             plan=ShardingPlan.from_strategy(strategy, model_spec))


@pytest.fixture(scope="module")
def sharded():
    runner = _runner(FullySharded(), 4)
    state, losses = runner.init(_params()), []
    for i in range(3):
        state, loss = runner.run(state, _batch(i))
        losses.append(float(loss))
    return runner, state, losses


def test_three_adamw_steps_are_the_single_device_steps(sharded):
    _, state, losses = sharded
    single = _runner(AllReduce(), 1)
    want = single.init(_params())
    for i in range(3):
        want, loss = single.run(want, _batch(i))
        assert losses[i] == pytest.approx(float(loss), rel=1e-5)
    for name in ("w1", "b1", "w2"):
        np.testing.assert_allclose(np.asarray(state.params[name]),
                                   np.asarray(want.params[name]),
                                   rtol=1e-4, atol=1e-4)   # of 3e-2 moved


def test_every_large_leaf_and_its_moments_are_a_quarter_a_device(sharded):
    runner, state, _ = sharded
    adam = state.opt_state[0]
    for tree in (state.params, adam.mu, adam.nu):
        for name, rows in (("w1", D), ("w2", 2 * D)):
            leaf = tree[name]
            assert leaf.sharding.spec == P("data", None)
            assert len(leaf.sharding.device_set) == 4
            assert {s.data.shape for s in leaf.addressable_shards} == \
                {(rows // 4, leaf.shape[1])}
        assert tree["b1"].sharding.spec == P()          # small: whole
    plan = runner.plan
    assert set(plan.data_sharded) == {"w1", "w2"}
    assert plan.data_shard_axes() == {(D, 2 * D): 0, (2 * D, D): 0}
    assert plan.params["w1"].opt_pspec == plan.params["w1"].pspec == P("data", None)
    assert not plan.zero and not _runner(AllReduce(), 4).plan.data_sharded


def test_the_compiled_step_gathers_the_weights_and_reduces_the_gradients(sharded):
    runner, state, _ = sharded
    text = runner.compiled_step(state, runner.shard_batch(_batch(0))).as_text()
    gathered = re.findall(r"= f32\[(\d+),(\d+)\]\S* all-gather\(", text)
    assert {(int(a), int(b)) for a, b in gathered} == {(D, 2 * D), (2 * D, D)}
    assert " all-reduce(" in text or " reduce-scatter(" in text
    assert " all-to-all(" not in text      # activations keep the batch sharding


def test_the_gauges_are_the_plans_arithmetic(sharded):
    runner, _, _ = sharded
    stored = 2 * D * 2 * D * 4                 # w1 and w2, float32
    assert runner.plan.data_shard_bytes(runner._model_spec, 4) == stored * 3 // 4
    assert telemetry.gauge("step.param_gather_bytes").value == stored * 3 // 4
    assert telemetry.gauge("step.grad_scatter_bytes").value == stored * 3 // 4
    assert runner.plan.data_shard_bytes(runner._model_spec, 1) == 0


def test_the_rule_is_a_function_of_the_shape_alone():
    assert data_shard_axis((2560, 10240), 4) == 0
    assert data_shard_axis((65536, 2560), 4) == 0
    assert data_shard_axis((5120, 16), 4) is None             # 81,920 < 2^18
    assert data_shard_axis((2560,), 4) is None
    assert data_shard_axis((3, 1 << 20), 4) == 1              # 4 does not divide 3
    assert data_shard_axis((1 << 20,), 1) is None
    assert data_shard_axis((512, 512), 4) == 0 and MIN_SHARDED_SIZE == 512 * 512
    assert data_shard_axis((512, 511), 4) is None


def test_a_caller_that_lays_out_by_the_rule_is_not_moved_by_init(sharded):
    runner, _, _ = sharded
    layout = {name: NamedSharding(runner.mesh, P() if data_shard_axis(
        leaf.shape, 4) is None else P("data", None))
        for name, leaf in _params().items()}
    placed = jax.device_put(_params(), layout)
    state = runner.init(placed)
    for name in placed:
        assert state.params[name].sharding == placed[name].sharding
    # and the caller's copy survives the donated steps
    state, _ = runner.run(state, _batch(0))
    np.testing.assert_array_equal(np.asarray(placed["w1"]), _params()["w1"])


def test_per_device_gathers_a_stored_leaf_in_the_body_and_scatters_its_gradient():
    mesh = mesh_lib.build_mesh(axes={"data": 4}, devices=jax.devices()[:4])
    rows = jnp.arange(8 * 6, dtype=jnp.float32).reshape(8, 6) / 48.0
    table = jnp.linspace(-1.0, 1.0, 12 * 6, dtype=jnp.float32).reshape(12, 6)

    def kernel(rows, table):                 # a body the compiler must not split
        assert table.shape == (12, 6)        # whole on every device
        return jnp.tanh(rows @ table.T)

    def loss(rows, table):
        return jnp.sum(mesh_lib.per_device(kernel, (rows, table), (True, False)) ** 2)

    want = jax.grad(lambda r, t: jnp.sum(jnp.tanh(r @ t.T) ** 2),
                    argnums=(0, 1))(rows, table)
    for stored in (None, {(12, 6): 0}):
        with mesh, mesh_lib.stored_shards(stored):
            jaxpr = str(jax.make_jaxpr(jax.grad(loss, argnums=1))(rows, table))
            got = jax.jit(jax.grad(loss, argnums=(0, 1)))(rows, table)
        assert ("all_gather" in jaxpr) == bool(stored)
        # the transpose of the body's gather: summed onto the shares
        assert (("reduce_scatter" in jaxpr) or ("psum_scatter" in jaxpr)) \
            == bool(stored)
        for a, b in zip(got, want):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    # outside the context nothing is declared
    assert mesh_lib._STORED_SHARDS.get() is None


def test_constrain_batch_is_the_identity_without_a_mesh_and_in_a_shard_map():
    x = jnp.ones((8, 4))
    assert mesh_lib.constrain_batch(x) is x
    mesh = mesh_lib.build_mesh(axes={"data": 4}, devices=jax.devices()[:4])
    with mesh:
        assert mesh_lib.constrain_batch(jnp.ones((3, 4))).shape == (3, 4)
        out = jax.jit(mesh_lib.constrain_batch)(x)
        assert out.sharding.spec == P(("data",)) or out.sharding.spec == P("data")
        inside = jax.shard_map(mesh_lib.constrain_batch, mesh=mesh,
                               in_specs=P("data"), out_specs=P("data"))(x)
        np.testing.assert_array_equal(inside, x)
