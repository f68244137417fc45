"""EvaByte (``models/evabyte.py``) against the plain reference
(``benchmark/reference/evabyte.py``) on seeded weights: the loss and every
gradient under both attention forms and under per-layer recomputation; the
eight heads' targets and valid positions at the sequence's end; the four
shares of a layer's heads add up to the uncut layer; the cell's configuration
counts the parameters its file states; ``RMSNorm(unit_offset=True)``; and a
one-head family lowers the step it lowered before the shell learned of more."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.models import decoder, deepseek_v3, evabyte
from autodist_tpu.models.common import RMSNorm
from benchmark.reference import evabyte as reference

TINY = dict(vocab_size=40, d_model=64, n_layers=2, n_heads=4, heads_held=2,
            first_head_held=2, head_dim=16, d_ff=96, window=32, chunk=4,
            n_pred_heads=3, dtype=jnp.float32, max_len=256)


def _model(**changes):
    cfg = evabyte.EvaByteConfig(**{**TINY, **changes})
    model, params = evabyte.init_params(cfg, rng=jax.random.PRNGKey(1))
    # the norms' offsets, phi and mu away from their small starts, the
    # matrices large enough that attention is far from uniform
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 64))
    params = jax.tree_util.tree_map(
        lambda x: x + 0.3 * jax.random.normal(next(keys), x.shape)
        if x.ndim == 1 or x.shape[-1] == cfg.head_dim else 6.0 * x, params)
    return cfg, model, params


def _reference_loss(cfg):
    def loss(params, batch):
        with jax.default_matmul_precision("highest"):
            return reference.loss(
                params, batch, n_layers=cfg.n_layers, head_dim=cfg.head_dim,
                window=cfg.window, chunk=cfg.chunk,
                n_pred_heads=cfg.n_pred_heads, rope_theta=cfg.rope_theta,
                rms_eps=cfg.rms_eps)
    return loss


@pytest.mark.parametrize("impl,remat", [("dot", False), ("kernel", False),
                                        ("kernel", True)])
def test_loss_and_every_gradient_match_the_reference(impl, remat):
    cfg, model, params = _model(attention_impl=impl, remat=remat)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(3), (2, 97), 0, 40)}
    loss, grads = jax.jit(jax.value_and_grad(evabyte.make_loss_fn(model)))(
        params, batch)
    want, want_grads = jax.jit(jax.value_and_grad(_reference_loss(cfg)))(
        params, batch)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    got = dict(jax.tree_util.tree_leaves_with_path(grads))
    for path, ref in jax.tree_util.tree_leaves_with_path(want_grads):
        assert np.any(ref), path
        np.testing.assert_allclose(got[path], ref, rtol=0, atol=2e-5 * float(
            jnp.max(jnp.abs(ref))), err_msg=jax.tree_util.keystr(path))
    assert telemetry.gauge("loss.pred_heads").value == 3
    assert telemetry.gauge("attention.heads_held").value == 2
    if remat:
        assert telemetry.gauge("remat.layers").value == 2
    # 2 sequences x 2 heads held x 2 layers of 96 positions
    visible, computed = evabyte.eva_pairs(96, 32, 4)
    assert telemetry.gauge("eva.pairs.visible").value == 8 * visible
    assert telemetry.gauge("eva.pairs.computed").value == 8 * computed


def test_the_heads_targets_and_valid_positions_at_the_sequences_end():
    """Head ``j`` at position ``t`` is scored against token ``t + 1 + j``:
    by hand on logits that put all mass on one id a head."""
    length, vocab, heads = 6, 5, 3
    tokens = jnp.asarray([[0, 1, 2, 3, 4, 0, 1]])         # [1, L + 1]
    # every head predicts, at every position, the token (t + 1 + j) % vocab:
    # right wherever the batch has that token
    at = np.arange(length)[:, None] + 1 + np.arange(heads)[None, :]
    logits = 20.0 * jax.nn.one_hot(at % vocab, vocab)      # [L, heads, vocab]
    loss = decoder.ahead_nll(logits.reshape(1, length, heads * vocab), tokens,
                             heads)
    assert float(loss) < 1e-6
    # wrong at the LAST position a head can score and nowhere else: head j's
    # last valid position is L - 1 - j, its mean over L - j positions
    for j in range(heads):
        wrong = logits.at[length - 1 - j, j].set(
            20.0 * jax.nn.one_hot((at[length - 1 - j, j] + 1) % vocab, vocab))
        loss = decoder.ahead_nll(wrong.reshape(1, length, heads * vocab),
                                 tokens, heads)
        assert float(loss) == pytest.approx(20.0 / (length - j) / heads, rel=1e-4)
    # ... and past it nothing is scored: head 2 at the last two positions
    ignored = logits.at[length - 2:, 2].set(0.0)
    assert float(decoder.ahead_nll(
        ignored.reshape(1, length, heads * vocab), tokens, heads)) < 1e-6


def test_four_shares_of_the_heads_add_up_to_the_uncut_layer():
    """Each of four chips holds one head of a four-head layer: its columns of
    W_q, W_k, W_v, its phi and mu, its rows of W_o. The four attention
    outputs add up to the uncut reference's for the whole layer."""
    cfg = evabyte.EvaByteConfig(**{**TINY, "heads_held": 4, "first_head_held": 0})
    _, _, params = _model(heads_held=4, first_head_held=0)
    whole = params["block_0"]["attn"]
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 64, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want = reference.attention(h, whole, head_dim=16, theta=cfg.rope_theta,
                                   window=32, chunk=4)
    total = 0.0
    for first in range(4):
        share_cfg = evabyte.EvaByteConfig(
            **{**TINY, "heads_held": 1, "first_head_held": first})
        cols = slice(16 * first, 16 * (first + 1))
        share = {name: {"kernel": whole[name]["kernel"][:, cols]}
                 for name in ("query", "key", "value")}
        share.update(out={"kernel": whole["out"]["kernel"][cols]},
                     phi=whole["phi"][first:first + 1],
                     mu=whole["mu"][first:first + 1])
        total = total + evabyte.EvaAttention(share_cfg).apply(
            {"params": share}, h)
    np.testing.assert_allclose(total, want, atol=1e-5 * float(
        jnp.max(jnp.abs(want))))
    with pytest.raises(ValueError, match="not among the layer's"):
        evabyte.EvaByteConfig(**{**TINY, "heads_held": 2, "first_head_held": 3})


def test_the_cells_configuration_counts_the_parameters_its_file_states():
    cfg = evabyte.EvaByteConfig(n_layers=4, heads_held=8)
    shapes = jax.eval_shape(lambda key: evabyte.init_params(cfg, rng=key)[1],
                            jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == 620_015_616
    attn = shapes["block_0"]["attn"]
    assert attn["query"]["kernel"].shape == (4096, 1024)
    assert attn["out"]["kernel"].shape == (1024, 4096)
    assert attn["phi"].shape == attn["mu"].shape == (8, 128)
    assert shapes["lm_head"]["kernel"].shape == (4096, 8 * 320)
    assert {x.dtype for x in jax.tree_util.tree_leaves(shapes)} == {
        jnp.dtype("float32")}


def test_init_follows_the_published_std_and_the_unit_offset():
    cfg, _, _ = _model()
    _, params = evabyte.init_params(
        evabyte.EvaByteConfig(**{**TINY, "d_model": 256, "d_ff": 512}))
    assert float(jnp.std(params["block_0"]["mlp"]["up"]["kernel"])) == \
        pytest.approx(0.01275, rel=0.05)
    assert float(jnp.std(params["embed"]["embedding"])) == \
        pytest.approx(0.01275, rel=0.1)
    for norm in (params["ln_f"], params["block_1"]["ln_attn"]):
        assert not np.any(norm["scale"])
    phi = params["block_0"]["attn"]["phi"]
    assert float(jnp.max(jnp.abs(phi))) <= cfg.head_dim ** -0.5 + 1e-7


@pytest.mark.parametrize("unit_offset", [False, True])
def test_rms_norm_with_and_without_the_unit_offset(unit_offset):
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 8)) * 3.0
    norm = RMSNorm(1e-5, jnp.float32, unit_offset)
    params = norm.init(jax.random.PRNGKey(1), x)["params"]
    assert np.all(params["scale"] == (0.0 if unit_offset else 1.0))
    w = jnp.linspace(-0.5, 0.5, 8)
    want = x / np.sqrt(np.mean(np.square(x), axis=-1, keepdims=True) + 1e-5) \
        * ((1.0 + w) if unit_offset else w)
    np.testing.assert_allclose(norm.apply({"params": {"scale": w}}, x), want,
                               rtol=1e-6, atol=1e-6)


# SHA-256 of a one-head family's lowered loss and gradient, locations and the
# module's name left out. At the commit before the shell took a family's
# init, offset, head count and logits dtype (353583a) it was caf9bf2a...11ac,
# and stayed so through that change; PR 51 changed the share's routing alone
# (the chosen scores by a select, the sorted keys from the sort: 4 gathers and
# 2 scatters fewer in this text, 2 reductions and 2 barriers more), and this
# is its text's.
ONE_HEAD_SHA256 = "ba054613fccbbe730468e6e80c5976847495b95f772095d42523f72bb9b6ba7d"


def test_a_one_head_family_lowers_what_it_lowered_before():
    """The latent-attention family: the shell, a dense ``GatedMLP`` layer, a
    routed share with its shared expert, the untied one-head loss."""
    cfg = deepseek_v3.DeepseekV3Config(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, kv_lora_rank=12, n_dense_layers=1,
        d_ff=48, d_expert=16, n_experts_routed=4, experts_held=2, top_k=2,
        n_shared_experts=1, rows_bound=16, max_len=32, dtype=jnp.float32)
    model, params = deepseek_v3.init_params(cfg, rng=jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((2, 17), jnp.int32)}
    text = jax.jit(jax.value_and_grad(deepseek_v3.make_loss_fn(model))).lower(
        params, batch).as_text()
    text = re.sub(r"loc\(.*?\)|#loc.*|module @\S+", "", text)
    assert hashlib.sha256(text.encode()).hexdigest() == ONE_HEAD_SHA256
