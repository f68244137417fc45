"""End-to-end minimum slice — value-exact parity with reference c0.

The reference proved correctness by asserting the post-step variable equals the
hand-computed averaged-gradient update (``tests/integration/cases/c0.py:88-121``).
Same here: one SGD step over an 8-way sharded batch must produce exactly the update
computed from the full-batch gradient with numpy, for every strategy family.
"""

import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import AutoDist
from autodist_tpu.strategy import (AllReduce, Parallax, PartitionedAR, PartitionedPS,
                                   PS, PSLoadBalancing, RandomAxisPartitionAR,
                                   UnevenPartitionedPS)

LR = 0.1
BATCH = 16


def _data(seed=123):
    rng = np.random.RandomState(seed)
    x = rng.randn(BATCH).astype(np.float32)
    y = (3.0 * x + 2.0 + 0.1 * rng.randn(BATCH)).astype(np.float32)
    return {"x": x, "y": y}


def _loss(p, batch):
    pred = batch["x"] * p["w"] + p["b"]
    return jnp.mean((batch["y"] - pred) ** 2)


def _expected_after_one_step(batch, w0=0.0, b0=0.0):
    # d/dw mean((y - (wx+b))^2) = mean(-2x(y - wx - b)); at w0=b0=0: -2 mean(x*y)
    x, y = batch["x"], batch["y"]
    resid = y - (w0 * x + b0)
    gw = np.mean(-2.0 * x * resid)
    gb = np.mean(-2.0 * resid)
    return w0 - LR * gw, b0 - LR * gb


STRATEGIES = [
    PS, PSLoadBalancing, PartitionedPS, UnevenPartitionedPS,
    AllReduce, PartitionedAR, RandomAxisPartitionAR, Parallax,
]


@pytest.mark.parametrize("builder_cls", STRATEGIES, ids=lambda c: c.__name__)
def test_one_step_matches_hand_computed_update(builder_cls):
    batch = _data()
    ad = AutoDist(strategy_builder=builder_cls())
    params = {"w": jnp.zeros(()), "b": jnp.zeros(())}
    step = ad.function(_loss, params, optax.sgd(LR), example_batch=batch)
    step(batch)
    got = step.get_state().params
    want_w, want_b = _expected_after_one_step(batch)
    np.testing.assert_allclose(float(got["w"]), want_w, rtol=1e-5)
    np.testing.assert_allclose(float(got["b"]), want_b, rtol=1e-5)


def test_loss_decreases_over_ten_steps():
    batch = _data()
    ad = AutoDist(strategy_builder=AllReduce())
    params = {"w": jnp.zeros(()), "b": jnp.zeros(())}
    step = ad.function(_loss, params, optax.sgd(0.05), example_batch=batch)
    losses = [float(step(batch)) for _ in range(10)]
    assert losses[-1] < losses[0]
    assert losses == sorted(losses, reverse=True)  # monotone for this convex problem


def test_bf16_compressor_approximates_dense_update():
    batch = _data()
    ad = AutoDist(strategy_builder=AllReduce(compressor="HorovodCompressor"))
    params = {"w": jnp.zeros(()), "b": jnp.zeros(())}
    step = ad.function(_loss, params, optax.sgd(LR), example_batch=batch)
    step(batch)
    got = step.get_state().params
    want_w, want_b = _expected_after_one_step(batch)
    # bf16 wire format: ~3 decimal digits
    np.testing.assert_allclose(float(got["w"]), want_w, rtol=2e-2)
    np.testing.assert_allclose(float(got["b"]), want_b, rtol=2e-2)


def test_error_feedback_caught_up_after_many_steps():
    """EF compensates the bf16 rounding over time: parameters track the uncompressed
    run closely (reference compressor.py:120-143 semantics)."""
    batch = _data()
    params = {"w": jnp.zeros(()), "b": jnp.zeros(())}

    ad_ref = AutoDist(strategy_builder=AllReduce())
    step_ref = ad_ref.function(_loss, params, optax.sgd(0.05), example_batch=batch)
    ad_ef = AutoDist(strategy_builder=AllReduce(compressor="HorovodCompressorEF"))
    step_ef = ad_ef.function(_loss, params, optax.sgd(0.05), example_batch=batch)

    for _ in range(20):
        step_ref(batch)
        step_ef(batch)
    w_ref = float(step_ref.get_state().params["w"])
    w_ef = float(step_ef.get_state().params["w"])
    assert abs(w_ref - w_ef) < 5e-3


def test_linear_regression_example_runs():
    import examples.linear_regression as lr
    losses = lr.main()
    assert losses[-1] < losses[0]


def test_multi_param_model_with_embedding_parallax():
    """Sparse embedding + dense layers under the Parallax hybrid, 2 steps."""
    rng = np.random.RandomState(0)
    vocab, dim = 50, 8
    params = {
        "emb": jnp.asarray(rng.randn(vocab, dim), jnp.float32),
        "w": jnp.asarray(rng.randn(dim, 1), jnp.float32),
        "b": jnp.zeros((1,)),
    }
    idx = rng.randint(0, vocab, size=(BATCH,))
    y = rng.randn(BATCH, 1).astype(np.float32)
    batch = {"idx": idx, "y": y}

    def loss(p, b):
        e = jnp.take(p["emb"], b["idx"], axis=0)
        pred = e @ p["w"] + p["b"]
        return jnp.mean((b["y"] - pred) ** 2)

    ad = AutoDist(strategy_builder=Parallax())
    step = ad.function(loss, params, optax.sgd(0.1), example_batch=batch)
    l0 = float(step(batch))
    l1 = float(step(batch))
    assert l1 < l0
    # the strategy actually routed the embedding to PS
    strat = ad._strategy
    kinds = {n.var_name: n.WhichOneof("synchronizer") for n in strat.node_config}
    assert kinds["emb"] == "ps_synchronizer"
    assert kinds["w"] == "all_reduce_synchronizer"
