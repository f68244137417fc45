"""The Ling / Ring hybrid family (``models/bailing_hybrid.py``) against the
plain reference (``benchmark/reference/bailing_hybrid.py``) on seeded weights:
the loss and every gradient at a size with both mixers, a dense and an expert
layer and a grouped router, plain and under per-layer recomputation; the
``W_o`` partial sums of two head shares of a KDA layer, and of a latent layer,
add up to the uncut reference's layer; the routed parts of all expert shares
and the shared expert counted once add up to the uncut reference's FFN; the
cell's configuration counts the parameters its file states."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.models import bailing_hybrid as bh
from autodist_tpu.models.deepseek_v3 import LatentAttention
from autodist_tpu.models.moe import GatedMLP, RoutedShare
from benchmark.reference import bailing_hybrid as reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(vocab_size=48, d_model=64, n_layers=3, layer_group_size=2,
            n_heads=4, heads_held=2, first_head_held=2, head_dim=16,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            kv_lora_rank=12, n_dense_layers=1, d_ff=96, d_expert=16,
            d_shared=16, n_experts_routed=16, experts_held=4,
            first_expert_held=4, top_k=4, n_group=4, topk_group=2,
            rows_bound=64, max_len=256, dtype=jnp.float32)


def _config(**changes):
    return bh.BailingHybridConfig(**{**TINY, **changes})


@functools.lru_cache(maxsize=None)
def _model(**changes):
    cfg = _config(**changes)
    model, params = bh.init_params(cfg, rng=jax.random.PRNGKey(1))
    # the matrices large enough that every gate, decay and score is far from
    # its start; the biases, norms and decay parameters moved off theirs
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 256))
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.2 * jax.random.normal(next(keys), x.shape)
        if x.ndim == 1 or path[-1].key.endswith("_conv") else 5.0 * x, params)
    return cfg, model, params


def _reference_arguments(cfg):
    return dict(
        layer_types=cfg.kinds, n_dense_layers=cfg.n_dense_layers,
        n_heads=cfg.heads_held, head_dim=cfg.head_dim,
        kda_lower_bound=cfg.kda_lower_bound,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        kv_lora_rank=cfg.kv_lora_rank, top_k=cfg.top_k, n_group=cfg.n_group,
        topk_group=cfg.topk_group, rms_eps=cfg.rms_eps,
        rope_theta=cfg.rope_theta, route_norm=cfg.route_norm,
        route_scale=cfg.route_scale, first_expert_held=cfg.first_expert_held)


@functools.lru_cache(maxsize=None)
def _reference_loss_and_grads():
    """One program for both cases below: recomputation changes no number."""
    cfg, _, params = _model()

    def loss(params, batch):
        with jax.default_matmul_precision("highest"):
            return reference.loss(params, batch, **_reference_arguments(cfg))

    return jax.jit(jax.value_and_grad(loss))(params, _batch())


def _batch():
    return {"tokens": jax.random.randint(jax.random.PRNGKey(3), (2, 81), 0, 48)}


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_gradient_match_the_reference(remat):
    cfg, model, params = _model(remat=remat)
    assert cfg.kinds == ("kda", "mla", "kda")
    loss, grads = jax.jit(jax.value_and_grad(bh.make_loss_fn(model)))(
        params, _batch())
    want, want_grads = _reference_loss_and_grads()
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    got = dict(jax.tree_util.tree_leaves_with_path(grads))
    for path, ref in jax.tree_util.tree_leaves_with_path(want_grads):
        assert np.any(ref), path
        np.testing.assert_allclose(got[path], ref, rtol=0, atol=3e-5 * float(
            jnp.max(jnp.abs(ref))), err_msg=jax.tree_util.keystr(path))
    assert telemetry.gauge("kda.heads_held").value == 2
    assert telemetry.gauge("attention.heads_held").value == 2
    assert telemetry.gauge("moe.route.groups").value == 4
    assert telemetry.gauge("moe.route.groups_kept").value == 2
    assert telemetry.gauge("kda.chunks").value == 2 * 2      # 80 positions, chunk 64
    assert telemetry.gauge("kda.state_kept_bytes").value == 2 * 2 * 2 * 16 * 16 * 4
    if remat:
        assert telemetry.gauge("remat.layers").value == 3
        assert telemetry.gauge("remat.kept_bytes").value > 0


def _head_columns(first, held, width):
    return slice(first * width, (first + held) * width)


def test_two_shares_of_a_kda_layers_heads_add_up_to_the_uncut_layer():
    """Each of two chips holds two heads of a four-head KDA layer: their
    columns of the five wide projections and of ``W_beta``, their taps,
    ``A_log`` and ``dt_bias``, their rows of ``W_o``; the norm's weight is
    every chip's alike. The two outputs add up to the uncut reference's."""
    cfg, _, params = _model(heads_held=4, first_head_held=0)
    whole = params["block_0"]["kda"]
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 70, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want = reference.kimi_delta_attention(
            h, whole, n_heads=4, head_dim=16, lower_bound=-5.0, eps=cfg.rms_eps)
    total = 0.0
    for first in (0, 2):
        cols, heads = _head_columns(first, 2, 16), slice(first, first + 2)
        share = {name: {"kernel": whole[name]["kernel"][:, cols]}
                 for name in ("query", "key", "value", "gate")}
        share.update(
            {f"{name}_conv": whole[f"{name}_conv"][cols]
             for name in ("query", "key", "value")},
            decay=whole["decay"][:, cols], dt_bias=whole["dt_bias"][cols],
            A_log=whole["A_log"][heads], beta=whole["beta"][:, heads],
            out_norm=whole["out_norm"],
            out={"kernel": whole["out"]["kernel"][cols]})
        total = total + jax.jit(bh.KimiDeltaAttention(
            _config(heads_held=2, first_head_held=first)).apply)(
                {"params": share}, h)
    np.testing.assert_allclose(total, want, atol=2e-5 * float(
        jnp.max(jnp.abs(want))))
    with pytest.raises(ValueError, match="not among the layer's"):
        _config(heads_held=2, first_head_held=3)


def test_two_shares_of_a_latent_layers_heads_add_up_to_the_uncut_layer():
    """``query``, ``kv_up``, ``gate`` and ``out`` are the held heads'; the
    latent, its norm and the rotary key are every chip's alike."""
    cfg, _, params = _model(heads_held=4, first_head_held=0)
    whole = params["block_1"]["attn"]
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 48, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want = reference.gated_latent_attention(
            h, whole, n_heads=4, d_nope=8, d_rope=4, d_v=8, rank=12,
            eps=cfg.rms_eps, theta=cfg.rope_theta)
    total = 0.0
    for first in (0, 2):
        share = dict(
            whole, query={"kernel": whole["query"]["kernel"][
                :, _head_columns(first, 2, 12)]},
            kv_up={"kernel": whole["kv_up"]["kernel"][
                :, _head_columns(first, 2, 16)]},
            gate={"kernel": whole["gate"]["kernel"][:, first:first + 2]},
            out={"kernel": whole["out"]["kernel"][_head_columns(first, 2, 8)]})
        total = total + jax.jit(LatentAttention(
            cfg, heads_held=2, head_gate=True).apply)({"params": share}, h)
    np.testing.assert_allclose(total, want, atol=2e-5 * float(
        jnp.max(jnp.abs(want))))


def test_all_expert_shares_and_the_shared_expert_once_add_up_to_the_uncut_ffn():
    """Two chips hold eight of the sixteen experts each (two whole groups):
    the routed parts of the two shares and the shared expert counted once
    are the uncut reference's FFN."""
    cfg, _, params = _model(experts_held=16, first_expert_held=0)
    whole = params["block_1"]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(7), (2, 40, cfg.d_model))
    route = dict(top_k=4, n_group=4, topk_group=2, route_norm=True,
                 route_scale=cfg.route_scale, first_expert_held=0)
    with jax.default_matmul_precision("highest"):
        routed, _ = reference.mixture(h.reshape(80, -1), whole, **route)
        want = reference.gated_mlp(h, whole["shared"]) + routed.reshape(h.shape)
    total = 0.0
    for first in (0, 8):
        share = dict(whole, **{name: whole[name][first:first + 8]
                               for name in ("gate", "up", "down")})
        share.pop("shared")
        m, _ = jax.jit(RoutedShare(
            _config(experts_held=8, first_expert_held=first), 0).apply)(
                {"params": share}, h)
        total = total + m
    shared = GatedMLP(cfg.d_shared, cfg.dtype).apply(
        {"params": whole["shared"]}, h)
    np.testing.assert_allclose(total + shared, want, atol=2e-5 * float(
        jnp.max(jnp.abs(want))))


def test_the_cells_configuration_counts_the_parameters_its_file_states():
    from benchmark.families import bailing_hybrid as family
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ling-3.0-flash.json")) as f:
        config = json.load(f)
    cfg = family.model_config(config)
    shapes = jax.eval_shape(lambda key: bh.init_params(cfg, rng=key)[1],
                            jax.random.PRNGKey(0))
    count = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert count == config["parameters"]
    assert f"{count:,}" in config["reduced_why"]
    held = config["num_attention_heads"]
    kda = shapes["block_0"]["kda"]
    assert kda["query"]["kernel"].shape == (2560, held * 128)
    assert kda["decay"].shape == (2560, held * 128)
    assert kda["beta"].shape == (2560, held)
    assert kda["key_conv"].shape == (held * 128, 4)
    assert kda["out"]["kernel"].shape == (held * 128, 2560)
    attn = shapes["block_5"]["attn"]
    assert attn["query"]["kernel"].shape == (2560, held * 192)
    assert attn["kv_down"]["kernel"].shape == (2560, 576)
    assert attn["kv_up"]["kernel"].shape == (512, held * 256)
    assert attn["gate"]["kernel"].shape == (2560, held)
    assert shapes["block_1"]["moe"]["router"].shape == (2560, 512)
    assert shapes["block_1"]["moe"]["up"].shape == (8, 2560, 768)
    assert "mlp" in shapes["block_0"] and "moe" in shapes["block_6"]
    assert shapes["lm_head"]["kernel"].shape == (2560, 19648)
    assert {x.dtype for x in jax.tree_util.tree_leaves(shapes)} == {
        jnp.dtype("float32")}


def test_init_starts_the_decay_as_kimi_linears_code_does():
    _, params = bh.init_params(_config(heads_held=4, first_head_held=0,
                                       head_dim=64))
    kda = params["block_0"]["kda"]
    assert 0.0 <= float(kda["A_log"].min()) and \
        float(kda["A_log"].max()) <= np.log(16.0) + 1e-6
    dt = jax.nn.softplus(kda["dt_bias"])
    assert 1e-4 <= float(dt.min()) and float(dt.max()) <= 0.1 + 1e-6
    assert np.all(kda["out_norm"] == 1.0)
    assert not np.any(params["block_1"]["moe"]["expert_bias"])


def test_the_first_layers_take_float32s_value_and_the_ordinary_derivative():
    """Under bfloat16 the leading KDA layers' sublayers are computed a second
    time to float32's precision (``PRECISE_LAYERS``): the block's output is
    the float32 block's, each sublayer's derivative the ordinary bfloat16
    one's."""
    cfg, _, params = _model()
    half = _config(dtype=jnp.bfloat16)
    assert [flags[2] for flags in bh.BailingHybrid(half).layers()] == [
        True, False, False]                         # kda, mla, kda past the two
    assert [flags[2] for flags in bh.BailingHybrid(_config(
        dtype=jnp.bfloat16, layer_group_size=6)).layers()] == [True, True, False]
    assert not any(flags[2] for flags in bh.BailingHybrid(cfg).layers())
    block = params["block_0"]
    x = 0.02 * jax.random.normal(jax.random.PRNGKey(8), (2, 70, cfg.d_model))
    w = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def run(config, precise):
        def total(p, x):
            out, _ = bh.BailingHybridBlock(config, "kda", True, precise).apply(
                {"params": p}, x)
            return jnp.sum(out * w), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            total, argnums=(0, 1), has_aux=True))(block, x)
        assert all(np.all(np.isfinite(g)) for g in jax.tree_util.tree_leaves(grads))
        return out, grads

    exact, _ = run(cfg, False)
    ordinary, _ = run(half, False)
    precise, _ = run(half, True)
    scale = float(jnp.max(jnp.abs(exact)))
    assert float(jnp.max(jnp.abs(precise - exact))) < 2e-4 * scale
    assert float(jnp.max(jnp.abs(ordinary - exact))) > 2e-3 * scale
    with pytest.raises(ValueError, match="a precise layer is a KDA layer"):
        bh.BailingHybridBlock(half, "mla", False, True).init(
            jax.random.PRNGKey(0), x)

    # the mechanism alone: the second call's value, the first call's derivative
    def sublayer(h, precise=False):
        return jnp.sin(h.astype(jnp.float32)) + (0.25 if precise else 0.0)

    value, slope = jax.value_and_grad(lambda h: bh.float32_valued(
        sublayer, h, jnp.bfloat16, True).sum())(jnp.float32(0.3))
    assert float(value) == pytest.approx(np.sin(0.3) + 0.25, rel=1e-6)
    assert float(slope) == pytest.approx(
        np.cos(float(jnp.bfloat16(0.3))), rel=4e-3)     # through a bfloat16 cast
