"""Sparse gradient wire path: all-gather (indices, rows) + segment-sum.

The reference synced sparse (IndexedSlices) gradients as an all-gather of
indices+values (``all_reduce_synchronizer.py:132-173``) so an embedding gradient
crossed the wire at ~rows-touched size, not the full matrix. These tests prove
the TPU-native equivalent: value-exactness vs the dense path (including
duplicate indices), and — by HLO inspection — that the collective carries
batch-sized rows while no vocab-sized all-reduce remains.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import AutoDist
from autodist_tpu.model_spec import ModelSpec
from autodist_tpu.parallel import synchronization
from autodist_tpu.parallel.mesh import build_mesh
from autodist_tpu.parallel.plan import ShardingPlan
from autodist_tpu.strategy import AllReduce, Parallax

VOCAB, DIM, BATCH = 793, 8, 32
LR = 0.1


def _params():
    rng = np.random.RandomState(0)
    return {"emb": jnp.asarray(rng.randn(VOCAB, DIM), jnp.float32),
            "w": jnp.asarray(rng.randn(DIM, 1), jnp.float32)}


def _batch(seed=3, with_duplicates=False):
    rng = np.random.RandomState(seed)
    idx = rng.randint(0, VOCAB, size=(BATCH,))
    if with_duplicates:
        idx[::3] = idx[0]  # force cross-shard duplicate rows
    return {"idx": idx, "y": rng.randn(BATCH, 1).astype(np.float32)}


def _loss(p, b):
    e = jnp.take(p["emb"], b["idx"], axis=0)
    return jnp.mean((b["y"] - e @ p["w"]) ** 2)


def _plan_and_mesh(builder):
    from autodist_tpu.resource_spec import ResourceSpec
    spec = ResourceSpec("nodes: [{address: localhost, tpus: 8, chief: true}]")
    model = ModelSpec.from_loss_fn(_loss, _params(), _batch())
    strategy = builder.build(model, spec)
    plan = ShardingPlan.from_strategy(strategy, model)
    mesh = build_mesh(axes=dict(plan.mesh_axes))
    return plan, model, mesh


def test_index_leaf_detected_and_wire_enabled():
    plan, _, _ = _plan_and_mesh(Parallax())
    p = plan.params["emb"]
    assert p.sparse
    assert p.index_leaf == "idx"
    assert "emb" in plan.sparse_wire_params
    assert "w" not in plan.sparse_wire_params


@pytest.mark.parametrize("builder_cls", [Parallax, AllReduce])
@pytest.mark.parametrize("dup", [False, True], ids=["unique", "duplicates"])
def test_sparse_sync_value_exact(builder_cls, dup):
    """The (indices, rows) wire reconstructs exactly the dense pmean gradient."""
    plan, model, mesh = _plan_and_mesh(builder_cls())
    params, batch = _params(), _batch(with_duplicates=dup)
    grad_fn = synchronization.make_grad_fn(plan, model, mesh, _loss)

    ef = synchronization.init_ef_state(plan, params, mesh=mesh)
    from jax.sharding import NamedSharding
    batch_sharded = {k: jax.device_put(v, NamedSharding(mesh, plan.batch_pspec(np.ndim(v))))
                     for k, v in batch.items()}
    with mesh:
        grads, loss, _, _ = jax.jit(grad_fn)(params, batch_sharded, ef)

    dense = jax.grad(_loss)(params, batch)
    np.testing.assert_allclose(np.asarray(grads["emb"]), np.asarray(dense["emb"]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(grads["w"]), np.asarray(dense["w"]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(loss), float(_loss(params, batch)), rtol=1e-5)


def test_wire_carries_rows_not_matrix():
    """HLO proof of wire volume: the embedding gradient crosses as batch rows
    (all-gather of [local_batch, DIM] + indices); no vocab-sized all-reduce."""
    plan, model, mesh = _plan_and_mesh(Parallax())
    params, batch = _params(), _batch()
    grad_fn = synchronization.make_grad_fn(plan, model, mesh, _loss)
    ef = synchronization.init_ef_state(plan, params, mesh=mesh)
    hlo = jax.jit(grad_fn).lower(params, batch, ef).compile().as_text()

    collective_lines = [l for l in hlo.splitlines()
                        if "all-reduce" in l or "all-gather" in l]
    assert any("all-gather" in l for l in collective_lines), hlo[:2000]
    # No collective touches a [VOCAB, DIM] operand.
    for line in collective_lines:
        assert f"{VOCAB},{DIM}" not in line.replace(" ", ""), line


def test_end_to_end_parallax_training_with_sparse_wire():
    params, batch = _params(), _batch(with_duplicates=True)
    ad = AutoDist(strategy_builder=Parallax())
    step = ad.function(_loss, params, optax.sgd(LR), example_batch=batch)
    l0 = float(step(batch))
    for _ in range(5):
        l1 = float(step(batch))
    assert l1 < l0
    # One-step parity against the hand-computed dense update.
    want = jax.tree_util.tree_map(
        lambda p, g: np.asarray(p) - LR * np.asarray(g),
        params, jax.grad(_loss)(params, batch))
    ad2 = AutoDist(strategy_builder=Parallax())
    step2 = ad2.function(_loss, params, optax.sgd(LR), example_batch=batch)
    step2(batch)
    got = step2.get_state().params
    np.testing.assert_allclose(np.asarray(got["emb"]), want["emb"], rtol=1e-5, atol=1e-6)


def test_transformed_indices_disable_sparse_wire():
    """idx+1 is not value-equal to the batch leaf: provenance must drop the
    mapping so the dense (always-correct) path is used."""
    from autodist_tpu.model_spec import detect_sparse_index_sources

    def loss(p, b):
        e = jnp.take(p["emb"], b["idx"] + 1, axis=0)
        return jnp.mean((b["y"] - e @ p["w"]) ** 2)

    params = _params()
    batch = _batch()
    assert detect_sparse_index_sources(loss, params, batch) == {}
    # And the full pipeline stays value-exact via the dense fallback.
    spec_model = ModelSpec.from_loss_fn(loss, params, batch)
    assert spec_model.params["emb"].index_leaf is None


def test_two_index_leaves_disable_sparse_wire():
    """A table gathered with two different batch leaves cannot use the single-leaf
    wire format; the mapping must be dropped entirely."""
    from autodist_tpu.model_spec import detect_sparse_index_sources

    def loss(p, b):
        e1 = jnp.take(p["emb"], b["idx"], axis=0)
        e2 = jnp.take(p["emb"], b["idx2"], axis=0)
        return jnp.mean(((e1 + e2) @ p["w"]) ** 2)

    params = _params()
    batch = {"idx": np.zeros((BATCH,), np.int32),
             "idx2": np.ones((BATCH,), np.int32),
             "y": np.zeros((BATCH, 1), np.float32)}
    assert detect_sparse_index_sources(loss, params, batch) == {}


def test_negative_indices_value_exact():
    """jnp.take wraps negative indices; the wire format reproduces the wrap."""
    plan, model, mesh = _plan_and_mesh(Parallax())
    params = _params()
    rng = np.random.RandomState(11)
    batch = {"idx": rng.randint(-VOCAB, VOCAB, size=(BATCH,)),
             "y": rng.randn(BATCH, 1).astype(np.float32)}
    assert "emb" in plan.sparse_wire_params
    grad_fn = synchronization.make_grad_fn(plan, model, mesh, _loss)
    ef = synchronization.init_ef_state(plan, params, mesh=mesh)
    from jax.sharding import NamedSharding
    sharded = {k: jax.device_put(v, NamedSharding(mesh, plan.batch_pspec(np.ndim(v))))
               for k, v in batch.items()}
    with mesh:
        grads, _, _, _ = jax.jit(grad_fn)(params, sharded, ef)
    dense = jax.grad(_loss)(params, batch)
    np.testing.assert_allclose(np.asarray(grads["emb"]), np.asarray(dense["emb"]),
                               rtol=1e-5, atol=1e-6)
