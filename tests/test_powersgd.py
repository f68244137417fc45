"""PowerSGD compressor — rank-r low-rank gradient sync with error feedback.

The reference drafted ``PowerSGDCompressor`` but shipped it commented out
(``kernel/synchronization/compressor.py:208-284``); this build implements it
(``parallel/synchronization.py``). These tests prove: the factorized wire format is
actually used, matrix parameters still learn, error feedback keeps the compressed
run tracking the exact run, and vectors/scalars bypass factorization (exact sync,
like the reference draft's rank>=2 gate).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import AutoDist
from autodist_tpu.parallel.synchronization import (EFState, PowerSGDState,
                                                   init_ef_state)
from autodist_tpu.strategy import AllReduce

BATCH = 16
DIM_IN, DIM_OUT = 8, 4


def _data(seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(BATCH, DIM_IN).astype(np.float32)
    w_true = rng.randn(DIM_IN, DIM_OUT).astype(np.float32)
    y = (x @ w_true + 0.01 * rng.randn(BATCH, DIM_OUT)).astype(np.float32)
    return {"x": x, "y": y}


def _loss(p, batch):
    pred = batch["x"] @ p["w"] + p["b"]
    return jnp.mean((batch["y"] - pred) ** 2)


def _params():
    return {"w": jnp.zeros((DIM_IN, DIM_OUT)), "b": jnp.zeros((DIM_OUT,))}


def test_powersgd_state_shapes():
    """Matrix params get PowerSGDState (per-replica residual + [n, r] Q); the
    vector bias gets a plain scalar placeholder (exact sync path)."""
    batch = _data()
    ad = AutoDist(strategy_builder=AllReduce(compressor="PowerSGDCompressor",
                                             power_sgd_rank=2))
    step = ad.function(_loss, _params(), optax.sgd(0.1), example_batch=batch)
    state = step.runner.init(_params())
    ef = state.ef_state
    assert isinstance(ef["w"], PowerSGDState)
    dp = step.runner.plan.dp_size
    assert ef["w"].error.shape == (dp, DIM_IN, DIM_OUT)
    assert ef["w"].q.shape == (DIM_OUT, 2)
    # Q warm start is orthonormal.
    qtq = np.asarray(ef["w"].q.T @ ef["w"].q)
    np.testing.assert_allclose(qtq, np.eye(2), atol=1e-5)
    assert not isinstance(ef["b"], (PowerSGDState, EFState))
    assert np.asarray(ef["b"]).shape == ()


def test_powersgd_rank_clamped_to_matrix_dims():
    ad = AutoDist(strategy_builder=AllReduce(compressor="PowerSGDCompressor",
                                             power_sgd_rank=64))
    batch = _data()
    step = ad.function(_loss, _params(), optax.sgd(0.1), example_batch=batch)
    state = step.runner.init(_params())
    # rank clamps to min(64, m, n) = DIM_OUT
    assert state.ef_state["w"].q.shape == (DIM_OUT, DIM_OUT)


def test_powersgd_loss_decreases():
    batch = _data()
    ad = AutoDist(strategy_builder=AllReduce(compressor="PowerSGDCompressor",
                                             power_sgd_rank=1))
    step = ad.function(_loss, _params(), optax.sgd(0.05), example_batch=batch)
    # Rank-1 factorization of a rank-4 problem: EF drip-feeds the residual, so
    # convergence is slower than exact sync but steady.
    losses = [float(step(batch)) for _ in range(60)]
    assert losses[-1] < losses[0] * 0.15


def test_powersgd_full_rank_with_ef_tracks_exact_run():
    """With warm-started Q, one power iteration per step, and error feedback, the
    full-rank PowerSGD run converges to the same parameters as the exact run."""
    batch = _data()

    ad_ref = AutoDist(strategy_builder=AllReduce())
    step_ref = ad_ref.function(_loss, _params(), optax.sgd(0.05), example_batch=batch)
    ad_psgd = AutoDist(strategy_builder=AllReduce(compressor="PowerSGDCompressor",
                                                  power_sgd_rank=DIM_OUT))
    step_psgd = ad_psgd.function(_loss, _params(), optax.sgd(0.05), example_batch=batch)

    for _ in range(40):
        step_ref(batch)
        step_psgd(batch)
    w_ref = np.asarray(step_ref.get_state().params["w"])
    w_psgd = np.asarray(step_psgd.get_state().params["w"])
    np.testing.assert_allclose(w_psgd, w_ref, atol=5e-3)


def test_powersgd_bias_syncs_exactly():
    """The 1-D bias bypasses factorization: after one step it must match the exact
    (uncompressed) update to float precision, whatever happens to the matrix."""
    batch = _data()
    ad_ref = AutoDist(strategy_builder=AllReduce())
    step_ref = ad_ref.function(_loss, _params(), optax.sgd(0.1), example_batch=batch)
    ad_psgd = AutoDist(strategy_builder=AllReduce(compressor="PowerSGDCompressor"))
    step_psgd = ad_psgd.function(_loss, _params(), optax.sgd(0.1), example_batch=batch)
    step_ref(batch)
    step_psgd(batch)
    np.testing.assert_allclose(np.asarray(step_psgd.get_state().params["b"]),
                               np.asarray(step_ref.get_state().params["b"]),
                               rtol=1e-5)


def test_bf16_ef_residual_is_per_replica():
    """BF16_EF residuals carry a leading dp dim sharded over the data axes: each
    replica owns its own residual (the reference kept one residual per worker
    process, compressor.py:120-143)."""
    batch = _data()
    ad = AutoDist(strategy_builder=AllReduce(compressor="HorovodCompressorEF"))
    step = ad.function(_loss, _params(), optax.sgd(0.1), example_batch=batch)
    state = step.runner.init(_params())
    dp = step.runner.plan.dp_size
    assert isinstance(state.ef_state["w"], EFState)
    assert state.ef_state["w"].error.shape == (dp, DIM_IN, DIM_OUT)
    # After a step over distinct per-replica batch shards the residuals differ.
    state2, _ = step.runner.run(state, batch)
    err = np.asarray(state2.ef_state["w"].error)
    assert err.shape[0] == dp
    if dp > 1:
        assert not np.allclose(err[0], err[1])


def test_init_ef_state_plain_params_no_compression():
    ad = AutoDist(strategy_builder=AllReduce())
    batch = _data()
    step = ad.function(_loss, _params(), optax.sgd(0.1), example_batch=batch)
    state = step.runner.init(_params())
    leaves = jax.tree_util.tree_leaves(state.ef_state)
    assert all(np.asarray(l).shape == () for l in leaves)


@pytest.mark.parametrize("name", ["PowerSGDCompressor", "power_sgd"])
def test_builder_accepts_powersgd_spellings(name):
    AllReduce(compressor=name)


def test_ef_state_sized_by_actual_mesh_not_plan():
    """A strategy built for 8 devices can run on a smaller local mesh (the runner
    rebuilds it, runner.py:_mesh_from_plan); residuals must be sized per the mesh the
    state lives on, not the plan's original dp size."""
    from autodist_tpu.parallel.mesh import build_mesh
    from autodist_tpu.parallel.plan import ShardingPlan
    from autodist_tpu.model_spec import ModelSpec
    from autodist_tpu.runner import DistributedRunner

    params = _params()
    spec_model = ModelSpec(params)
    strategy = AllReduce(compressor="PowerSGDCompressor").build(
        spec_model, AutoDist().resource_spec)  # built for all 8 visible devices
    plan = ShardingPlan.from_strategy(strategy, spec_model)
    small_mesh = build_mesh(axes={"data": 4}, devices=jax.devices()[:4])
    runner = DistributedRunner(strategy, spec_model, _loss, optax.sgd(0.05),
                               mesh=small_mesh, plan=plan)
    state = runner.init(params)
    assert state.ef_state["w"].error.shape == (4, DIM_IN, DIM_OUT)
    batch = _data()
    state2, loss = runner.run(state, batch)
    assert np.isfinite(float(loss))
    assert state2.ef_state["w"].error.shape == (4, DIM_IN, DIM_OUT)


def test_powersgd_matrix_without_state_raises():
    """A matrix POWER_SGD param whose ef leaf is not a PowerSGDState must raise, not
    silently fall back to uncompressed sync (mirror of the BF16_EF guard)."""
    from autodist_tpu.parallel import synchronization
    from autodist_tpu.parallel.plan import ShardingPlan
    from autodist_tpu.model_spec import ModelSpec
    from autodist_tpu.parallel.mesh import build_mesh

    params = _params()
    spec_model = ModelSpec(params)
    strategy = AllReduce(compressor="PowerSGDCompressor").build(
        spec_model, AutoDist().resource_spec)
    plan = ShardingPlan.from_strategy(strategy, spec_model)
    mesh = build_mesh(axes={"data": len(jax.devices())})
    grad_fn = synchronization.make_grad_fn(plan, spec_model, mesh, _loss)
    bad_ef = jax.tree_util.tree_map(
        lambda _: jnp.zeros(()), params)  # bypassed init_ef_state
    with pytest.raises(TypeError, match="PowerSGDState"):
        grad_fn(params, _data(), bad_ef)
