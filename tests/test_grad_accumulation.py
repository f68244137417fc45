"""Gradient accumulation: k micro-batches, one update — value-exact vs one big batch.

The reference had no accumulation (its effective batch was replicas x feed); this is
a beyond-reference feature, so the correctness bar is self-imposed: for mean-reduced
losses the accumulated update must equal the full-batch update exactly (equal-sized
micro-batches make the mean of synced micro-gradients the full-batch gradient).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import AutoDist
from autodist_tpu.strategy import AllReduce, Parallax, PartitionedPS, PS

BATCH = 32


def _dense_data(seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.randn(BATCH, 4).astype(np.float32),
            "y": rng.randn(BATCH, 1).astype(np.float32)}


def _dense_loss(p, b):
    pred = b["x"] @ p["w"] + p["b"]
    return jnp.mean((b["y"] - pred) ** 2)


def _dense_params():
    rng = np.random.RandomState(7)
    return {"w": rng.randn(4, 1).astype(np.float32),
            "b": np.zeros((1,), np.float32)}


def _run_steps(strategy, accum, n_steps=3, optimizer=None, seed=0):
    ad = AutoDist(strategy_builder=strategy)
    runner = ad.create_distributed_session(
        _dense_loss, _dense_params(), optimizer or optax.sgd(0.1),
        example_batch=_dense_data(), accumulation_steps=accum)
    state = runner.init(_dense_params())
    losses = []
    for i in range(n_steps):
        state, loss = runner.run(state, _dense_data(seed + i))
        losses.append(float(loss))
    return jax.device_get(runner.logical_params(state)), losses


@pytest.mark.parametrize("strategy_cls", [AllReduce, PS, PartitionedPS])
def test_accumulated_update_matches_full_batch(strategy_cls):
    params_full, losses_full = _run_steps(strategy_cls(), accum=1)
    params_acc, losses_acc = _run_steps(strategy_cls(), accum=4)
    for k in params_full:
        np.testing.assert_allclose(params_acc[k], params_full[k],
                                   rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(losses_acc, losses_full, rtol=2e-6, atol=2e-6)


def test_accumulation_with_adam_matches():
    params_full, _ = _run_steps(AllReduce(), accum=1, optimizer=optax.adam(1e-2))
    params_acc, _ = _run_steps(AllReduce(), accum=2, optimizer=optax.adam(1e-2))
    for k in params_full:
        np.testing.assert_allclose(params_acc[k], params_full[k],
                                   rtol=2e-6, atol=2e-6)


def test_sparse_wire_accumulation_matches():
    """Parallax routes the embedding over the sparse wire path inside the scan."""
    rng = np.random.RandomState(3)
    params = {"emb": rng.randn(61, 8).astype(np.float32),
              "w": rng.randn(8, 1).astype(np.float32)}
    batch = {"idx": rng.randint(0, 61, (BATCH,)),
             "y": rng.randn(BATCH, 1).astype(np.float32)}

    def loss_fn(p, b):
        rows = jnp.take(p["emb"], b["idx"], axis=0)
        return jnp.mean((b["y"] - rows @ p["w"]) ** 2)

    def run(accum):
        ad = AutoDist(strategy_builder=Parallax())
        runner = ad.create_distributed_session(
            loss_fn, params, optax.sgd(0.1), example_batch=batch,
            accumulation_steps=accum)
        state = runner.init(params)
        for _ in range(2):
            state, _ = runner.run(state, batch)
        return jax.device_get(runner.logical_params(state))

    full, acc = run(1), run(4)
    for k in full:
        np.testing.assert_allclose(acc[k], full[k], rtol=2e-6, atol=2e-6)


def test_compressed_accumulation_converges():
    """EF state threads through the micro scan (not value-exact by design)."""
    ad = AutoDist(strategy_builder=AllReduce(compressor="HorovodCompressorEF"))
    runner = ad.create_distributed_session(
        _dense_loss, _dense_params(), optax.sgd(0.05),
        example_batch=_dense_data(), accumulation_steps=4)
    state = runner.init(_dense_params())
    first = last = None
    for i in range(20):
        state, loss = runner.run(state, _dense_data())
        first = first if first is not None else float(loss)
        last = float(loss)
    assert last < first * 0.5


def test_fetches_see_logical_batch():
    ad = AutoDist(strategy_builder=AllReduce())
    runner = ad.create_distributed_session(
        _dense_loss, _dense_params(), optax.sgd(0.1),
        example_batch=_dense_data(), accumulation_steps=4)
    state = runner.init(_dense_params())
    batch = _dense_data()
    preds = lambda p, b: b["x"] @ p["w"] + p["b"]  # noqa: E731
    expected = jax.device_get(preds(
        {k: jnp.asarray(v) for k, v in _dense_params().items()},
        {k: jnp.asarray(v) for k, v in batch.items()}))
    state, (loss, fetched) = runner.run(state, batch, fetches=preds)
    assert fetched.shape == (BATCH, 1)
    np.testing.assert_allclose(jax.device_get(fetched), expected, rtol=1e-5, atol=1e-5)


def test_aux_shapes_match_accum1():
    """Scalar aux averages across micros; per-example aux folds back to [B]."""
    def loss_with_aux(p, b):
        pred = b["x"] @ p["w"] + p["b"]
        per_ex = ((b["y"] - pred) ** 2)[:, 0]
        return jnp.mean(per_ex), {"mean_abs": jnp.mean(jnp.abs(per_ex)),
                                  "per_example": per_ex}

    def run(accum):
        ad = AutoDist(strategy_builder=AllReduce())
        runner = ad.create_distributed_session(
            loss_with_aux, _dense_params(), optax.sgd(0.1),
            example_batch=_dense_data(), has_aux=True, accumulation_steps=accum)
        state = runner.init(_dense_params())
        _, (loss, aux) = runner.run(state, _dense_data())
        return jax.device_get(aux)

    a1, a4 = run(1), run(4)
    assert a4["per_example"].shape == a1["per_example"].shape == (BATCH,)
    np.testing.assert_allclose(a4["per_example"], a1["per_example"],
                               rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(a4["mean_abs"], a1["mean_abs"], rtol=2e-6, atol=2e-6)


def test_non_batch_leaves_stay_whole():
    """Auxiliary leaves (per-class weights, small constants) must not be
    micro-sliced: only leaves at the global batch size scan."""
    cw = np.array([1.0, 2.0, 3.0, 4.0], np.float32)

    def loss_fn(p, b):
        pred = b["x"] @ p["w"] + p["b"]
        return jnp.mean((b["y"] - pred) ** 2) * jnp.sum(b["cw"])

    def run(accum):
        ad = AutoDist(strategy_builder=AllReduce())
        batch = dict(_dense_data(), cw=cw, three=np.ones((3,), np.float32))
        runner = ad.create_distributed_session(
            loss_fn, _dense_params(), optax.sgd(0.01), example_batch=batch,
            accumulation_steps=accum)
        state = runner.init(_dense_params())
        state, loss = runner.run(state, batch)
        return float(loss), jax.device_get(runner.logical_params(state))

    (l1, p1), (l4, p4) = run(1), run(4)
    assert l1 == pytest.approx(l4, rel=1e-6)
    for k in p1:
        np.testing.assert_allclose(p4[k], p1[k], rtol=2e-6, atol=2e-6)


def test_vector_aux_averages_not_concats():
    """A fixed-size vector aux (not per-example) keeps its shape under accum."""
    def loss_with_aux(p, b):
        pred = b["x"] @ p["w"] + p["b"]
        err = (b["y"] - pred)[:, 0]
        return jnp.mean(err ** 2), jnp.stack([jnp.mean(err), jnp.max(err),
                                              jnp.min(err)])

    def run(accum):
        ad = AutoDist(strategy_builder=AllReduce())
        runner = ad.create_distributed_session(
            loss_with_aux, _dense_params(), optax.sgd(0.1),
            example_batch=_dense_data(), has_aux=True, accumulation_steps=accum)
        state = runner.init(_dense_params())
        _, (loss, aux) = runner.run(state, _dense_data())
        return jax.device_get(aux)

    a1, a4 = run(1), run(4)
    assert a1.shape == a4.shape == (3,)
    # Mean-of-micro-means equals the full mean for equal micro sizes.
    np.testing.assert_allclose(a4[0], a1[0], rtol=2e-6, atol=2e-6)


def test_longer_aux_leaf_is_not_mistaken_for_batch():
    """A sampled-softmax-style auxiliary leaf LONGER than the batch (and itself
    divisible by accum*dp) must never be silently micro-split in place of the
    true batch: two splittable dims is an explicit ambiguity error, and
    batch_size= resolves it to a value-exact accumulation (the long leaf stays
    whole in every micro-step)."""
    rng = np.random.RandomState(11)
    neg = rng.randn(64, 1).astype(np.float32)  # longer than BATCH=32

    def loss_fn(p, b):
        pred = b["x"] @ p["w"] + p["b"]
        # Every example is scored against ALL negatives every micro-step; if
        # b["neg"] were micro-sliced the penalty term would change value.
        penalty = jnp.mean((pred[:, None, :] - b["neg"][None, :, :]) ** 2)
        return jnp.mean((b["y"] - pred) ** 2) + 0.1 * penalty

    def run(accum, batch_size=None):
        ad = AutoDist(strategy_builder=AllReduce())
        batch = dict(_dense_data(), neg=neg)
        runner = ad.create_distributed_session(
            loss_fn, _dense_params(), optax.sgd(0.05), example_batch=batch,
            accumulation_steps=accum, batch_size=batch_size)
        state = runner.init(_dense_params())
        state, loss = runner.run(state, batch)
        return float(loss), jax.device_get(runner.logical_params(state))

    with pytest.raises(ValueError, match="[Aa]mbiguous"):
        run(2)  # both 32 and 64 are splittable: refuse to guess

    (l1, p1), (l2, p2) = run(1, batch_size=BATCH), run(2, batch_size=BATCH)
    assert l1 == pytest.approx(l2, rel=1e-6)
    for k in p1:
        np.testing.assert_allclose(p2[k], p1[k], rtol=2e-6, atol=2e-6)


def test_ambiguous_batch_dim_raises_and_batch_size_resolves():
    """Two equally-common, equally-splittable leading dims: refuse to guess;
    an explicit batch_size= disambiguates."""
    rng = np.random.RandomState(5)
    batch = {"x": rng.randn(BATCH, 4).astype(np.float32),
             "neg": rng.randn(2 * BATCH, 4).astype(np.float32)}

    def loss_fn(p, b):
        pred = b["x"] @ p["w"] + p["b"]
        return jnp.mean(pred ** 2) + jnp.mean((b["neg"] @ p["w"]) ** 2)

    ad = AutoDist(strategy_builder=AllReduce())
    runner = ad.create_distributed_session(
        loss_fn, _dense_params(), optax.sgd(0.05), example_batch=batch,
        accumulation_steps=2)
    state = runner.init(_dense_params())
    with pytest.raises(ValueError, match="[Aa]mbiguous"):
        runner.run(state, batch)

    ad2 = AutoDist(strategy_builder=AllReduce())
    runner2 = ad2.create_distributed_session(
        loss_fn, _dense_params(), optax.sgd(0.05), example_batch=batch,
        accumulation_steps=2, batch_size=BATCH)
    state2 = runner2.init(_dense_params())
    state2, loss = runner2.run(state2, batch)
    assert np.isfinite(float(loss))


def test_splittable_outlier_does_not_hijack_indivisible_batch():
    """When the true (modal) batch dim is NOT divisible by accum*dp but an
    auxiliary leaf is, the aux leaf must not be silently micro-split in the
    batch's place: the inference refuses and names both dims."""
    rng = np.random.RandomState(13)
    batch = {"x": rng.randn(24, 4).astype(np.float32),   # 24 % (2*8) != 0
             "y": rng.randn(24, 1).astype(np.float32),
             "neg": rng.randn(32, 1).astype(np.float32)}  # 32 % 16 == 0

    def loss_fn(p, b):
        pred = b["x"] @ p["w"] + p["b"]
        pen = jnp.mean((pred[:, None, :] - b["neg"][None, :, :]) ** 2)
        return jnp.mean((b["y"] - pred) ** 2) + 0.1 * pen

    ad = AutoDist(strategy_builder=AllReduce())
    runner = ad.create_distributed_session(
        loss_fn, _dense_params(), optax.sgd(0.05), example_batch=batch,
        accumulation_steps=2)
    state = runner.init(_dense_params())
    with pytest.raises(ValueError, match="most common leading dim"):
        runner.run(state, batch)


def test_indivisible_batch_raises():
    ad = AutoDist(strategy_builder=AllReduce())
    runner = ad.create_distributed_session(
        _dense_loss, _dense_params(), optax.sgd(0.1),
        example_batch=_dense_data(), accumulation_steps=3)
    state = runner.init(_dense_params())
    with pytest.raises(ValueError, match="accumulation_steps"):
        runner.run(state, _dense_data())  # 32 splits by dp=8 but not by 3*8


def test_run_many_composes_with_accumulation_bit_exact():
    """In-window canary for the fused multi-step path (the full suite in
    tests/test_unrolled.py sorts past the tier-1 time budget): run_many over
    an accumulating runner must be BIT-identical to the sequential steps —
    the scan is a dispatch transform, not a numeric one."""
    def run(fused):
        ad = AutoDist(strategy_builder=AllReduce())
        runner = ad.create_distributed_session(
            _dense_loss, _dense_params(), optax.adam(1e-2),
            example_batch=_dense_data(), accumulation_steps=2)
        state = runner.init(_dense_params())
        batches = [_dense_data(seed=i) for i in range(3)]
        if fused:
            state, losses = runner.run_many(state, batches)
            losses = list(jax.device_get(losses))
        else:
            losses = []
            for b in batches:
                state, loss = runner.run(state, b)
                losses.append(jax.device_get(loss))
        return jax.device_get(runner.logical_params(state)), losses

    p_seq, l_seq = run(fused=False)
    p_fused, l_fused = run(fused=True)
    np.testing.assert_array_equal(np.stack(l_fused), np.stack(l_seq))
    for k in p_seq:
        np.testing.assert_array_equal(p_fused[k], p_seq[k])


def test_async_regime_rejects_accumulation():
    ad = AutoDist(strategy_builder=PS(sync=False))
    with pytest.raises(ValueError, match="synchronous"):
        ad.create_distributed_session(
            _dense_loss, _dense_params(), optax.sgd(0.1),
            example_batch=_dense_data(), accumulation_steps=2)
