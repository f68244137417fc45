"""Jamba (``models/jamba.py``) against the plain float32 reference
(``benchmark/reference/jamba.py``) on seeded weights: loss, logits and every
gradient at a toy width with the published layer rule (14 layers, attention at
7), plain and with every kernel (interpret mode); the layer-type rule at 28
layers; the three inner norms; the parameter count at the published sizes by
shapes alone; the initialisers; per-layer recomputation changes no number and
keeps what the configuration names."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from autodist_tpu import telemetry  # noqa: E402
from autodist_tpu.models import jamba  # noqa: E402
from benchmark.reference import jamba as reference  # noqa: E402
from tests import reference_programs  # noqa: E402

# the published rule (attn_layer_period 14, offset 7) at a toy width
TOY = jamba.JambaConfig(vocab_size=203, d_model=64, n_layers=14, d_state=4,
                        dt_rank=8, n_heads=4, n_kv_heads=1, d_ff=96,
                        max_len=64, dtype=jnp.float32, chunk=16)
# three layers (M*M) for what does not need the published rule
SMALL = dataclasses.replace(TOY, n_layers=3, attn_period=3, attn_offset=1)
# two layers at the narrowest width the kernels take (1,024 channels)
KERNELS = jamba.JambaConfig(vocab_size=203, d_model=512, n_layers=2,
                            attn_period=2, attn_offset=1, d_state=16,
                            dt_rank=8, n_heads=4, n_kv_heads=1, d_ff=128,
                            max_len=128, dtype=jnp.float32, chunk=64)


def _reference_config(cfg):
    return dict(n_layers=cfg.n_layers, attn_period=cfg.attn_period,
                attn_offset=cfg.attn_offset, d_state=cfg.d_state,
                dt_rank=cfg.dt_rank, n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads, rms_eps=cfg.rms_eps)


def _relative(a, b):
    num = sum(float(jnp.sum(jnp.square(x - y))) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))
    den = sum(float(jnp.sum(jnp.square(y))) for y in jax.tree_util.tree_leaves(b))
    return (num / den) ** 0.5


def _case(cfg, seq_len, seed=0):
    model, params = jamba.init_params(cfg, rng=jax.random.PRNGKey(seed))
    batch = jamba.synthetic_batch(cfg, batch_size=2, seq_len=seq_len)
    return model, params, batch


@pytest.mark.parametrize("cfg,seq_len,options", [
    (TOY, 24, {}),
    (SMALL, 24, dict(remat=True)),
    (KERNELS, 64, dict(ssm_impl="pallas", attention_impl="flash",
                       fused_head=True, remat=True)),
], ids=["published-rule-plain", "remat", "every-kernel"])
def test_loss_and_gradients_match_the_plain_reference(cfg, seq_len, options):
    _, params, batch = _case(cfg, seq_len)
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.jit(jax.value_and_grad(
            lambda p: reference.loss(p, batch, **_reference_config(cfg))))(params)
        model = jamba.Jamba(dataclasses.replace(cfg, **options))
        got, got_g = jax.jit(jax.value_and_grad(jamba.make_loss_fn(model)))(
            params, batch)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert _relative(got_g, want_g) < 1e-4
    assert jax.tree_util.tree_structure(got_g) == jax.tree_util.tree_structure(want_g)


def test_logits_match_the_reference_layer_by_layer_rule():
    """The model's logits are the reference's blocks and tied head applied by
    hand: attention in layer 1 alone of these three."""
    model, params, batch = _case(SMALL, 24)
    tokens = batch["tokens"][:, :-1]

    @jax.jit
    def by_hand(params):
        x = params["embed"]["embedding"][tokens]
        kinds = dict(ssm=dict(d_state=4, dt_rank=8),
                     attn=dict(n_heads=4, n_kv_heads=1))
        for i in range(3):
            x = reference.block(params[f"block_{i}"], x, eps=SMALL.rms_eps,
                                is_attention=i == 1, **kinds)
        x = reference.rms_norm(x, params["final_norm"]["scale"], SMALL.rms_eps)
        return x @ params["embed"]["embedding"].T

    with jax.default_matmul_precision("highest"):
        logits, second = jax.jit(lambda p: model.apply({"params": p}, tokens))(
            params)
        want = by_hand(params)
    np.testing.assert_allclose(logits, want, rtol=2e-4, atol=2e-4)
    assert float(second) == 0.0
    _, published, _ = _case(TOY, 8)
    assert ["attn" in published[f"block_{i}"] for i in range(14)] == \
        [i == 7 for i in range(14)]


def test_the_layer_type_rule_at_the_published_28_layers():
    cfg = jamba.JambaConfig()
    assert cfg.n_layers == 28 and (cfg.attn_period, cfg.attn_offset) == (14, 7)
    assert [i for i in range(28) if cfg.is_attention(i)] == [7, 21]
    assert cfg.pattern == "MMMMMMM*MMMMMM" * 2
    assert (cfg.d_inner, cfg.head_dim, cfg.d_state, cfg.dt_rank) == \
        (5120, 128, 16, 160)
    with pytest.raises(ValueError, match="attn_offset"):
        jamba.JambaConfig(attn_period=4, attn_offset=4)
    with pytest.raises(ValueError, match="Unknown ssm_impl"):
        jamba.JambaConfig(ssm_impl="mosaic")


def test_the_three_inner_norms_and_every_leaf_the_equations_name():
    model, params, batch = _case(SMALL, 8)
    mamba = params["block_0"]["mamba"]
    e, n, r, d = TOY.d_inner, TOY.d_state, TOY.dt_rank, TOY.d_model
    assert jax.tree_util.tree_map(lambda x: x.shape, mamba) == {
        "in_proj": {"kernel": (d, 2 * e)}, "conv": (e, 4), "conv_bias": (e,),
        "x_proj": (e, r + 2 * n), "dt_norm": {"scale": (r,)},
        "b_norm": {"scale": (n,)}, "c_norm": {"scale": (n,)},
        "dt_proj": (r, e), "dt_bias": (e,), "A_log": (e, n), "D": (e,),
        "out_proj": {"kernel": (e, d)}}
    # the norms act: scaling one moves the loss
    loss = jax.jit(jamba.make_loss_fn(model))
    for norm in ("dt_norm", "b_norm", "c_norm"):
        moved = jax.tree_util.tree_map(lambda x: x, params)
        moved["block_0"]["mamba"][norm]["scale"] = \
            2.0 * params["block_0"]["mamba"][norm]["scale"]
        assert float(loss(moved, batch)) != float(loss(params, batch)), norm
    assert set(params["block_1"]["attn"]) == {"query", "key", "value", "out"}
    assert params["block_1"]["attn"]["key"]["kernel"].shape == (d, d // 4)
    assert set(params) == {"embed", "final_norm"} | {f"block_{i}" for i in range(3)}


@pytest.mark.parametrize("layers,total", [(28, 3_029_337_472),
                                          (14, 1_598_556_096)])
def test_the_published_sizes_have_the_parameters_the_issue_counts(layers, total):
    """By shapes alone (``jax.eval_shape``): a Mamba-1 mixer 41,241,792, an MLP
    62,914,560, the attention layer 76,682,240, the tied table 167,772,160."""
    cfg = jamba.JambaConfig(n_layers=layers)
    shapes = jax.eval_shape(lambda k: jamba.init_params(cfg, rng=k)[1],
                            jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(x.shape))  # noqa: E731
                             for x in jax.tree_util.tree_leaves(tree))
    assert count(shapes["block_0"]["mamba"]) == 41_241_792
    assert count(shapes["block_0"]["mlp"]) == 62_914_560
    assert count(shapes["block_0"]) == 104_161_472
    assert count(shapes["block_7"]) == 76_682_240
    assert count(shapes["embed"]) == 167_772_160
    assert count(shapes) == total
    assert {str(x.dtype) for x in jax.tree_util.tree_leaves(shapes)} == {"float32"}


def test_the_initialisation_is_mamba_1s():
    _, params, _ = _case(SMALL, 8, seed=5)
    mamba = params["block_2"]["mamba"]
    np.testing.assert_allclose(
        mamba["A_log"], np.broadcast_to(np.log(np.arange(1, 5)), (128, 4)),
        rtol=1e-6)
    np.testing.assert_array_equal(mamba["D"], np.ones(128))
    step = np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    assert (step >= 1e-3 * 0.999).all() and (step <= 0.1 * 1.001).all()
    assert np.abs(np.asarray(mamba["dt_proj"])).max() <= 8 ** -0.5
    assert np.abs(np.asarray(mamba["conv"])).max() <= 0.5
    assert float(jnp.std(mamba["in_proj"]["kernel"])) == pytest.approx(0.02, rel=0.1)


def test_recomputation_keeps_the_values_kept_names():
    """Under ``remat`` the checkpoint's policy keeps ``KEPT``'s values (gauge
    ``remat.kept_values``): the ``x W_x`` product of each Mamba-1 layer, the
    attention layer's q / k / v; without ``remat`` nothing is booked."""
    _, params, batch = _case(SMALL, 24)

    def kept(**options):
        telemetry.gauge("remat.kept_values").set(0)
        model = jamba.Jamba(dataclasses.replace(SMALL, **options))
        jax.make_jaxpr(jax.grad(jamba.make_loss_fn(model)))(params, batch)
        return telemetry.gauge("remat.kept_values").value

    assert kept() == 0
    assert kept(remat=True) == 2 + 3      # SMALL: two Mamba-1 layers, one attention
    assert jamba.Jamba.kept == jamba.KEPT == (
        jamba.KEPT_QKV, jamba.KEPT_FLASH, jamba.KEPT_X_PROJ)
    assert telemetry.gauge("jamba.mamba_layers").value == 2
    assert telemetry.gauge("jamba.attention_layers").value == 1


def test_the_precise_product_is_float32s_in_three_bfloat16_passes():
    key_x, key_w = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(key_x, (48, 256), jnp.float32)
    w = jax.random.normal(key_w, (256, 96), jnp.float32) * 0.02
    with jax.default_matmul_precision("highest"):
        want = x @ w
    distance = lambda got: float(jnp.linalg.norm(got - want)  # noqa: E731
                                 / jnp.linalg.norm(want))
    # bfloat16 operands round at 2^-9 each
    assert distance(jamba._product(x, w, jnp.bfloat16)) > 1e-3
    assert distance(jamba._precise_product(x, w)) < 2e-5
    text = jax.jit(jamba._precise_product).lower(x, w).as_text()
    assert text.count("dot_general") == 3 and "reduce_precision" in text


def test_a_precise_layer_has_float32s_value_and_the_ordinary_derivative():
    cfg = dataclasses.replace(SMALL, d_model=256, d_ff=512, dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, cfg.d_model)) * 0.02
    blocks = {name: jamba.JambaBlock(config, False, precise) for
              name, config, precise in (
                  ("ordinary", cfg, False), ("precise", cfg, True),
                  ("float32", dataclasses.replace(cfg, dtype=jnp.float32), False))}
    params = blocks["ordinary"].init(jax.random.PRNGKey(6), x)
    cotangent = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)
    outs, pulled = {}, {}
    for name, block in blocks.items():
        @jax.jit
        def value_and_pullback(params, x, block=block):
            out, pullback = jax.vjp(lambda p, x: block.apply(p, x)[0], params, x)
            return out, pullback(cotangent)
        outs[name], pulled[name] = value_and_pullback(params, x)
    distance = lambda got: float(  # noqa: E731
        jnp.linalg.norm(got - outs["float32"]) / jnp.linalg.norm(outs["float32"]))
    assert distance(outs["ordinary"]) > 1e-3
    assert distance(outs["precise"]) < 3e-5
    for got, plain in zip(jax.tree_util.tree_leaves(pulled["precise"]),
                          jax.tree_util.tree_leaves(pulled["ordinary"])):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(plain))


def test_only_the_first_layer_is_precise_and_only_below_float32():
    flags = lambda cfg: [precise for _, precise in  # noqa: E731
                         jamba.Jamba(cfg).layers()]
    assert jamba.PRECISE_LAYERS == 1
    assert flags(dataclasses.replace(TOY, dtype=jnp.bfloat16)) == [True] + [False] * 13
    assert flags(TOY) == [False] * 14           # float32: exact as it is
    # the same parameters, whichever way a layer computes
    trees = [jax.tree_util.tree_structure(jax.eval_shape(
        lambda: jamba.init_params(dataclasses.replace(SMALL, dtype=dtype))[1]))
        for dtype in (jnp.float32, jnp.bfloat16)]
    assert trees[0] == trees[1]


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_the_precise_first_layer_brings_bfloat16_gradients_nearer(remat,
                                                                  monkeypatch):
    """The first layer's output is the stream (the embedding's rows are 0.02
    wide), so its rounding moves every later layer's Jacobian: computed to
    float32's precision, the whole gradient stands a third nearer the
    reference at this width and depth (a half at the published ones: PERF.md
    section 6, "PR 43")."""
    cfg = jamba.JambaConfig(vocab_size=203, d_model=512, n_layers=4,
                            attn_period=4, attn_offset=2, d_state=4, dt_rank=8,
                            n_heads=4, n_kv_heads=1, d_ff=1024, max_len=64,
                            dtype=jnp.bfloat16, chunk=16, remat=remat)
    _, params, batch = _case(cfg, 48)
    with jax.default_matmul_precision("highest"):
        # one program for [plain] and [remat]: the reference knows neither
        _, want = reference_programs.value_and_grad(
            "jamba", **_reference_config(cfg))(params, batch)
    distances = []
    for layers in (0, 1):
        monkeypatch.setattr(jamba, "PRECISE_LAYERS", layers)
        got = jax.jit(jax.grad(jamba.make_loss_fn(jamba.Jamba(cfg))))(params, batch)
        distances.append(_relative(got, want))
    assert distances[1] < 0.8 * distances[0]
    assert distances[0] < 0.03
