"""End-to-end sequence/context parallelism.

The long-context capability (prompt/SURVEY.md §5.7: absent from the reference, a
first-class requirement here): sequence sharded over the ``seq`` mesh axis, ring
attention rotating K/V shards, position embeddings globally offset, loss a global
token mean. Proven by value equivalence against the single-shard model.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import AutoDist
from autodist_tpu.models import transformer_lm
from autodist_tpu.parallel.sequence import (create_sequence_parallel_session,
                                            make_sequence_parallel_loss_fn)
from autodist_tpu.strategy import SequenceParallel

SEQ = 32
BATCH = 4


def _model(attention_impl):
    cfg = transformer_lm.TransformerLMConfig(
        vocab_size=128, d_model=16, n_heads=2, n_layers=2, d_ff=32,
        max_len=SEQ, dtype=jnp.float32, tied_output=False,
        attention_impl=attention_impl)
    return transformer_lm.init_params(cfg) + (cfg,)


def _batch(cfg, seed=0):
    # seq_len targets => tokens [B, SEQ+1] => inputs [B, SEQ], divisible by seq axis
    return transformer_lm.synthetic_batch(cfg, batch_size=BATCH, seq_len=SEQ,
                                          seed=seed)


def _value_and_grad(loss_fn):
    """Loss and gradients as ONE jitted program. Called eagerly, a
    ``shard_map`` over eight devices runs primitive by primitive, and the
    interpreted kernels inside it are thousands of them: the same values
    took 20 times as long (217 s against 13 s for the tied fused head)."""
    return jax.jit(jax.value_and_grad(loss_fn))


def test_sp_loss_and_grads_match_single_device():
    """SP loss/grads over a (data=2, seq=4) mesh == the plain single-shard model
    with identical parameters."""
    model_ring, params, cfg = _model("ring")
    model_dot, _, _ = _model("dot")
    batch = _batch(cfg)

    ref_loss_fn = transformer_lm.make_loss_fn(model_dot)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(ref_loss_fn))(params, batch)

    ad = AutoDist(strategy_builder=SequenceParallel(seq_axis_size=4))
    runner = create_sequence_parallel_session(ad, model_ring, params,
                                              optax.sgd(0.1))
    assert runner.mesh.shape["seq"] == 4
    sp_loss_fn = make_sequence_parallel_loss_fn(model_ring, runner.mesh)
    sp_loss, sp_grads = _value_and_grad(sp_loss_fn)(params, batch)

    np.testing.assert_allclose(float(sp_loss), float(ref_loss), rtol=1e-5)
    flat_ref = jax.tree_util.tree_leaves(ref_grads)
    flat_sp = jax.tree_util.tree_leaves(sp_grads)
    for a, b in zip(flat_ref, flat_sp):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("tied", [False, True])
def test_sp_fused_head_matches_plain_sp(tied):
    """The fused pallas head composes with sequence parallelism: same loss and
    gradients as the SP path with the XLA head — tied (embedding-table head,
    vd layout, gradient summing gather + fused dw) and untied."""
    import dataclasses
    _, _, cfg = _model("ring")
    cfg = dataclasses.replace(cfg, tied_output=tied)
    model_ring, params = transformer_lm.init_params(cfg)
    cfg_f = dataclasses.replace(cfg, fused_head=True)
    model_fused = transformer_lm.TransformerLM(cfg_f)
    batch = _batch(cfg)

    ad = AutoDist(strategy_builder=SequenceParallel(seq_axis_size=4))
    runner = create_sequence_parallel_session(ad, model_ring, params,
                                              optax.sgd(0.1))
    loss_plain = make_sequence_parallel_loss_fn(model_ring, runner.mesh)
    loss_fused = make_sequence_parallel_loss_fn(model_fused, runner.mesh)
    state = runner.init(params)
    p = runner.logical_params(state)
    with runner.mesh:
        lp, gp = _value_and_grad(loss_plain)(p, batch)
        lf, gf = _value_and_grad(loss_fused)(p, batch)
    np.testing.assert_allclose(float(lf), float(lp), rtol=1e-5)
    for a, e in zip(jax.tree_util.tree_leaves(gf), jax.tree_util.tree_leaves(gp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=5e-4, atol=5e-5)


def test_sp_training_decreases_loss():
    model, params, cfg = _model("ring")
    batch = _batch(cfg)
    ad = AutoDist(strategy_builder=SequenceParallel(seq_axis_size=4))
    runner = create_sequence_parallel_session(ad, model, params, optax.adam(1e-2))
    state = runner.init(params)
    losses = []
    for _ in range(6):
        state, loss = runner.run(state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert np.all(np.isfinite(losses))


def test_sp_composes_with_data_parallelism():
    """seq=2 leaves data=4: batch shards over data, sequence over seq, same loss."""
    model_ring, params, cfg = _model("ring")
    model_dot, _, _ = _model("dot")
    batch = _batch(cfg)
    ref = float(transformer_lm.make_loss_fn(model_dot)(params, batch))

    ad = AutoDist(strategy_builder=SequenceParallel(seq_axis_size=2))
    runner = create_sequence_parallel_session(ad, model_ring, params,
                                              optax.sgd(0.1))
    assert runner.mesh.shape["data"] == 4 and runner.mesh.shape["seq"] == 2
    loss_fn = make_sequence_parallel_loss_fn(model_ring, runner.mesh)
    np.testing.assert_allclose(float(jax.jit(loss_fn)(params, batch)), ref,
                               rtol=1e-5)


def test_sp_rejects_indivisible_sequence():
    model, params, cfg = _model("ring")
    ad = AutoDist(strategy_builder=SequenceParallel(seq_axis_size=4))
    runner = create_sequence_parallel_session(ad, model, params, optax.sgd(0.1))
    loss_fn = make_sequence_parallel_loss_fn(model, runner.mesh)
    bad = {"tokens": np.zeros((BATCH, 31), np.int32)}  # L=30 not divisible by 4
    with pytest.raises(ValueError, match="not divisible"):
        loss_fn(params, bad)


def test_sp_builder_validation():
    with pytest.raises(ValueError):
        SequenceParallel(seq_axis_size=0)
    with pytest.raises(ValueError):
        SequenceParallel(seq_axis_size=-2)
    model, params, cfg = _model("ring")
    from autodist_tpu.model_spec import ModelSpec
    from autodist_tpu import ResourceSpec
    with pytest.raises(ValueError, match="does not divide"):
        SequenceParallel(seq_axis_size=3).build(ModelSpec(params), ResourceSpec())


def test_sp_rejects_compressor():
    with pytest.raises(ValueError, match="compression"):
        SequenceParallel(seq_axis_size=2, compressor="HorovodCompressor")


def test_sp_rejects_sequence_beyond_max_len():
    """Out-of-range position offsets would silently clamp per-shard; the global
    length check fails loudly instead."""
    model, params, cfg = _model("ring")
    ad = AutoDist(strategy_builder=SequenceParallel(seq_axis_size=4))
    runner = create_sequence_parallel_session(ad, model, params, optax.sgd(0.1))
    loss_fn = make_sequence_parallel_loss_fn(model, runner.mesh)
    too_long = {"tokens": np.zeros((BATCH, 2 * SEQ + 1), np.int32)}
    with pytest.raises(ValueError, match="max_len"):
        loss_fn(params, too_long)


# ------------------------------------------------------------------ Ulysses

def test_ulysses_attention_matches_single_device():
    """All-to-all SP: seq-sharded ulysses attention == full attention."""
    from autodist_tpu.parallel.mesh import build_mesh
    from autodist_tpu.parallel.ulysses import make_ulysses_attention_fn
    from autodist_tpu.models.transformer_lm import (causal_mask,
                                                    dot_product_attention)
    rng = np.random.RandomState(0)
    B, L, H, D = 2, 32, 4, 8
    q, k, v = (jnp.asarray(rng.randn(B, L, H, D), jnp.float32) for _ in range(3))
    mesh = build_mesh(axes={"data": 2, "seq": 4})
    ul = make_ulysses_attention_fn(mesh, causal=True)(q, k, v)
    ref = dot_product_attention(q, k, v, causal_mask(L, jnp.float32), jnp.float32)
    np.testing.assert_allclose(np.asarray(ul), np.asarray(ref), atol=2e-5)


def test_ulysses_sp_loss_and_grads_match_single_device():
    """Full SP training path with attention_impl='ulysses'."""
    model_ul, params, cfg = _model("ulysses")
    model_dot, _, _ = _model("dot")
    batch = _batch(cfg)

    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        transformer_lm.make_loss_fn(model_dot)))(params, batch)

    ad = AutoDist(strategy_builder=SequenceParallel(seq_axis_size=2))
    runner = create_sequence_parallel_session(ad, model_ul, params, optax.sgd(0.1))
    sp_loss_fn = make_sequence_parallel_loss_fn(model_ul, runner.mesh)
    sp_loss, sp_grads = _value_and_grad(sp_loss_fn)(params, batch)

    np.testing.assert_allclose(float(sp_loss), float(ref_loss), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(ref_grads),
                    jax.tree_util.tree_leaves(sp_grads)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-4, atol=2e-5)


def test_ulysses_rejects_indivisible_heads():
    from autodist_tpu.parallel.mesh import build_mesh
    from autodist_tpu.parallel.ulysses import make_ulysses_attention_fn
    rng = np.random.RandomState(0)
    q = k = v = jnp.asarray(rng.randn(2, 32, 3, 8), jnp.float32)  # 3 heads, seq=4
    mesh = build_mesh(axes={"data": 2, "seq": 4})
    with pytest.raises(ValueError, match="divisible"):
        make_ulysses_attention_fn(mesh)(q, k, v)
