"""The DeepSeek-V3 family with latent attention (``models/deepseek_v3.py``):
the system's loss and whole gradient against the plain reference the benchmark
checks it with on the chip (``benchmark/reference/deepseek_v3.py``), the
parameter tree the equations name, interleaved-pair RoPE against rotate-half,
one chip's share (the 16 shares of a 128-wide router, with attention and the
shared experts counted once, add up to the uncut layer), per-layer
recomputation and what it keeps, the code the four sigmoid-routed families
share (``models/moe.py``) and a step through the normal path. Tiny widths on
the CPU mesh; kernels in interpret mode."""

import dataclasses
import functools
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import AutoDist, telemetry, train
from autodist_tpu.models import (afmoe, decoder, deepseek_v3, lfm2_moe, moe,
                                 nemotron_h, olmoe)
from autodist_tpu.models.common import keeping, rope, rope_pairs
from autodist_tpu.strategy import AllReduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tests import reference_programs  # noqa: E402

# One dense layer and two expert layers; keys 24 wide (16 + 8 shared) over
# values 16 through a latent of 24; the share: experts 2-4 of 8, top-3.
TINY = dict(vocab_size=256, d_model=64, n_layers=3, n_heads=4,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            kv_lora_rank=24, d_ff=96, d_expert=24, n_experts_routed=8,
            experts_held=3, first_expert_held=2, top_k=3, max_len=64)


def _rel_l2(a, b):
    leaves = lambda t: jax.tree_util.tree_leaves(t)  # noqa: E731
    num = sum(float(jnp.sum(jnp.square(x - y))) for x, y in zip(leaves(a), leaves(b)))
    return (num / sum(float(jnp.sum(jnp.square(y))) for y in leaves(b))) ** 0.5


def _reference_kwargs(cfg):
    return dict(n_heads=cfg.n_heads, qk_nope_head_dim=cfg.qk_nope_head_dim,
                qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
                kv_lora_rank=cfg.kv_lora_rank, n_layers=cfg.n_layers,
                n_dense_layers=cfg.n_dense_layers, top_k=cfg.top_k,
                rms_eps=cfg.rms_eps, rope_theta=cfg.rope_theta,
                route_norm=cfg.route_norm, route_scale=cfg.route_scale,
                first_expert_held=cfg.first_expert_held)


def _stirred(params, scale=0.2):
    """The leaves that init sets to constants (zeros, ones), drawn: an
    ``expert_bias`` large enough to change choices and norm weights, the
    latent's among them, that a dropped factor would show in."""
    def draw(path, x):
        if path[-1].key not in ("expert_bias", "scale"):
            return x
        key = jax.random.PRNGKey(sum(map(ord, jax.tree_util.keystr(path))))
        return x + scale * jax.random.normal(key, x.shape)
    return jax.tree_util.tree_map_with_path(draw, params)


def _batch(cfg, sequences=2, length=40, seed=3):
    return {"tokens": jnp.asarray(
        deepseek_v3.synthetic_batch(cfg, sequences, length, seed=seed)["tokens"])}


# The tolerances are the other share families': float32 activations agree to
# rounding, bfloat16 to parts in a thousand of the loss and a few percent of
# the gradient; a dropped term, scale or rotation moves either by far more.
@pytest.mark.parametrize("dtype,attention,fused,remat,loss_tol,grad_tol", [
    (jnp.float32, "dot", False, False, 1e-5, 2e-5),
    (jnp.float32, "flash", True, True, 1e-5, 2e-5),
    (jnp.bfloat16, "dot", False, False, 2e-3, 4e-2),
    (jnp.bfloat16, "flash", True, True, 2e-3, 4e-2),
], ids=["f32-dot", "f32-flash-remat", "bf16-dot", "bf16-flash-remat"])
def test_loss_and_gradients_match_the_plain_reference(dtype, attention, fused,
                                                      remat, loss_tol, grad_tol):
    cfg = deepseek_v3.DeepseekV3Config(dtype=dtype, attention_impl=attention,
                                       fused_head=fused, remat=remat,
                                       rows_bound=40, **TINY)
    model, params = deepseek_v3.init_params(cfg, jax.random.PRNGKey(1))
    params = _stirred(params)
    batch = _batch(cfg)
    loss, grads = jax.jit(jax.value_and_grad(deepseek_v3.make_loss_fn(model)))(
        params, batch)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = reference_programs.value_and_grad(
            "deepseek_v3", **_reference_kwargs(cfg))(params, batch)
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) <= loss_tol
    assert _rel_l2(grads, ref_grads) <= grad_tol
    assert {str(g.dtype) for g in jax.tree_util.tree_leaves(grads)} == {"float32"}
    # every leaf takes a gradient: the latent's norm and both halves of the
    # down projection (the rotary key's is the sum over the heads) among them
    attn = grads["block_1"]["attn"]
    for leaf in ("query", "kv_down", "kv_up", "out"):
        assert float(jnp.abs(attn[leaf]["kernel"]).max()) > 0, leaf
    assert float(jnp.abs(attn["kv_norm"]["scale"]).max()) > 0
    assert float(jnp.abs(attn["kv_down"]["kernel"][:, cfg.kv_lora_rank:]).max()) > 0
    d_bias = grads["block_1"]["moe"]["expert_bias"]
    assert abs(float(d_bias.sum())) < 1e-6 and float(jnp.abs(d_bias).max()) > 0


def test_the_tiny_stack_has_the_parameters_the_equations_name():
    cfg = deepseek_v3.DeepseekV3Config(**TINY)
    _, params = deepseek_v3.init_params(cfg)
    shapes = jax.tree_util.tree_map(lambda x: x.shape, params)
    attention = {"query": {"kernel": (64, 4 * 24)},
                 "kv_down": {"kernel": (64, 24 + 8)}, "kv_norm": {"scale": (24,)},
                 "kv_up": {"kernel": (24, 4 * 32)}, "out": {"kernel": (4 * 16, 64)}}
    mlp = lambda width: {"gate": {"kernel": (64, width)},  # noqa: E731
                         "up": {"kernel": (64, width)},
                         "down": {"kernel": (width, 64)}}
    norms = {"ln_attn": {"scale": (64,)}, "ln_mlp": {"scale": (64,)}}
    experts = {"router": (64, 8), "expert_bias": (8,), "gate": (3, 64, 24),
               "up": (3, 64, 24), "down": (3, 24, 64), "shared": mlp(2 * 24)}
    assert shapes == {
        "embed": {"embedding": (256, 64)},
        "block_0": {"attn": attention, "mlp": mlp(96), **norms},
        "block_1": {"attn": attention, "moe": experts, **norms},
        "block_2": {"attn": attention, "moe": experts, **norms},
        "ln_f": {"scale": (64,)}, "lm_head": {"kernel": (64, 256)}}
    assert {str(x.dtype) for x in jax.tree_util.tree_leaves(params)} == {"float32"}


def test_interleaved_pair_rope_is_rotate_half_under_the_de_interleaving():
    """``rope_interleave``: the published code moves the even columns before
    the odd ones and rotates halves; ``rope_pairs`` turns the same pairs by
    the same angles where they lie, and leaves the leading columns alone."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, 24))
    positions = jnp.arange(12) + 5
    perm = np.concatenate([np.arange(0, 16, 2), np.arange(1, 16, 2)])
    turned = rope_pairs(x, positions, 1e6, 16)
    np.testing.assert_array_equal(turned[..., :8], x[..., :8])
    np.testing.assert_allclose(turned[..., 8:][..., perm],
                               rope(x[..., 8:][..., perm], positions, 1e6),
                               rtol=1e-6, atol=1e-6)
    whole = rope_pairs(x[..., 8:], positions, 1e6)
    np.testing.assert_allclose(whole, turned[..., 8:], rtol=1e-6, atol=1e-6)
    # a rotation: lengths of the pairs stay, and position 0 turns nothing
    np.testing.assert_allclose(jnp.linalg.norm(whole, axis=-1),
                               jnp.linalg.norm(x[..., 8:], axis=-1), rtol=1e-5)
    np.testing.assert_allclose(rope_pairs(x, jnp.zeros(12), 1e6, 16), x, atol=1e-7)
    assert rope_pairs(x.astype(jnp.bfloat16), positions, 1e6, 16).dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="must be even"):
        rope_pairs(x, positions, 1e6, 15)


def test_the_sixteen_shares_with_attention_and_the_shared_experts_once_add_up():
    """What the guide asks of a share: the routed parts that the shares
    ``first_expert_held`` = 0, 8, ..., 120 of a 128-wide router give, with
    attention and the shared experts (which every chip computes alike)
    counted once, add up to what the uncut reference gives for the whole
    layer. The system's block on each share's slice of one parameter tree;
    the reference on the whole tree."""
    from benchmark.reference import deepseek_v3 as reference
    wide = dict(TINY, d_model=32, d_expert=16, n_experts_routed=128, top_k=6)
    cfg = deepseek_v3.DeepseekV3Config(dtype=jnp.float32, **dict(
        wide, experts_held=128, first_expert_held=0))
    d, tokens = cfg.d_model, 40
    x = jax.random.normal(jax.random.PRNGKey(3), (1, tokens, d))
    whole = _stirred(deepseek_v3.DeepseekV3Block(cfg, False).init(
        jax.random.PRNGKey(2), x[:, :4])["params"])
    banks = ("gate", "up", "down")

    def share(first, held, down=None):
        share_cfg = deepseek_v3.DeepseekV3Config(dtype=jnp.float32, **dict(
            wide, experts_held=held, first_expert_held=first, rows_bound=24))
        mine = {name: whole["moe"][name][first:first + held] for name in banks}
        if down is not None:
            mine["down"] = down
        params = dict(whole, moe=dict(whole["moe"], **mine))
        (out, _), sown = deepseek_v3.DeepseekV3Block(share_cfg, False).apply(
            {"params": params}, x, mutable=["intermediates"])
        return out, sown["intermediates"]["moe"]["load"][0]

    # what every chip computes alike: the stream, attention, the shared experts
    alike, _ = share(0, 8, down=jnp.zeros_like(whole["moe"]["down"][:8]))
    total, loads = alike, []
    for first in range(0, 128, 8):
        out, load = share(first, 8)
        total = total + (out - alike)
        loads.append(load)
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.block(
            whole, x, dense=False, eps=cfg.rms_eps,
            attn=dict(n_heads=cfg.n_heads, d_nope=cfg.qk_nope_head_dim,
                      d_rope=cfg.qk_rope_head_dim, d_v=cfg.v_head_dim,
                      rank=cfg.kv_lora_rank, theta=cfg.rope_theta),
            route=dict(top_k=cfg.top_k, route_norm=cfg.route_norm,
                       route_scale=cfg.route_scale, first_expert_held=0))
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=1e-5)
    # every rank makes the same choice over the whole width
    for load in loads:
        np.testing.assert_array_equal(load, loads[0])
    assert float(loads[0].sum()) == tokens * 6
    # and the whole bank in one layer is the same uncut result
    one, _ = share(0, 128)
    np.testing.assert_allclose(one, uncut, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dtype,kernels", [(jnp.float32, False),
                                           (jnp.bfloat16, True)],
                         ids=["f32-dot", "bf16-kernels"])
def test_recomputing_every_layer_changes_no_number(dtype, kernels):
    """The kept values are the values a second forward would make: bfloat16
    through the kernels agrees as float32 does (the tolerance is XLA's, which
    fuses the two programs differently, not bfloat16's)."""
    cfg = deepseek_v3.DeepseekV3Config(
        dtype=dtype, rows_bound=40,
        **(dict(attention_impl="flash", fused_head=True) if kernels else {}),
        **{**TINY, "n_layers": 2})      # the dense layer and one expert layer
    model, params = deepseek_v3.init_params(cfg, jax.random.PRNGKey(1))
    params = _stirred(params)
    batch = _batch(cfg, length=24, seed=5)
    plain = jax.jit(jax.value_and_grad(deepseek_v3.make_loss_fn(model)))(
        params, batch)
    telemetry.registry().clear()
    again = jax.jit(jax.value_and_grad(deepseek_v3.make_loss_fn(
        deepseek_v3.DeepseekV3(dataclasses.replace(cfg, remat=True)))))(
            params, batch)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7),
        plain, again)
    assert telemetry.gauge("remat.layers").value == 2
    # a token's q, latent and rotary key, and under flash o and a float32 lse
    # a head
    width = jnp.dtype(dtype).itemsize
    named = (4 * 24 + 24 + 8) * width
    assert telemetry.gauge("mla.kept_bytes_per_token").value == \
        named + (4 * 16 * width + 4 * 4 if kernels else 0)


def _made(kept, dense, attention="flash"):
    """What one layer under the model's policy hands its backward beside its
    arguments, by JAX's own account."""
    from jax._src.ad_checkpoint import saved_residuals
    cfg = deepseek_v3.DeepseekV3Config(attention_impl=attention, rows_bound=40,
                                       **TINY)
    block = deepseek_v3.DeepseekV3Block(cfg, dense)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, cfg.d_model))
    params = block.init(jax.random.PRNGKey(1), x)["params"]

    @functools.partial(jax.checkpoint, policy=keeping(kept))
    def layer(params, x):
        y, term = block.apply({"params": params}, x)
        return jnp.sum(jnp.square(y)) + term

    # (RoPE's four frequencies, a constant the layer computes from nothing,
    # once for q and once for the key, are kept by any policy: left out)
    return sorted((aval.shape, str(aval.dtype))
                  for aval, how in saved_residuals(layer, params, x)
                  if "from the argument" not in how
                  and aval.shape != (cfg.qk_rope_head_dim // 2,))


# (shape, dtype) of what a layer of the tiny stack keeps at 2 x 40 positions:
# q, the normed latent, the turned rotary key, flash's o and lse
_ATTENTION = [((2, 40, 96), "bfloat16"), ((2, 40, 24), "bfloat16"),
              ((2, 40, 1, 8), "bfloat16"), ((2, 40, 4, 16), "bfloat16"),
              ((8, 1, 40), "float32")]


# pass 0 of the share of 3 experts 24 wide at 40 rows a pass: the gathered rows
# and ``down``'s output, the gate and up products with what SiLU's rule and
# the product keep of them, weights, masks and indices (models/moe.py)
_PASS_0 = ([((40, 64), "bfloat16")] * 2 + [((40, 24), "bfloat16")] * 6
           + [((40,), "float32")] * 2 + [((40,), "bool")]
           + [((40,), "int32")] * 2
           + [((40, 1), "int32"), ((3,), "int32"), ((2,), "int32"), ((), "int32")])


@pytest.mark.parametrize("dense,rest", [
    (True, [((2, 40, 96), "bfloat16")] * 2),        # the MLP's gate and up
    # the router's logits, the shared pair's gate and up, the share's pass 0
    (False, [((80, 8), "float32")] + [((2, 40, 48), "bfloat16")] * 2 + _PASS_0),
], ids=["dense", "experts"])
def test_a_checkpointed_layer_keeps_the_listed_values_and_nothing_else(
        dense, rest):
    """Exactly the listed values: no K or V (the latent and the rotary key
    stand in for them), no sublayer's last product (``out``'s, ``down``'s:
    the next layer keeps the sum as its own input), nothing elementwise
    outside the share's pass."""
    telemetry.registry().clear()
    made = _made(deepseek_v3.KEPT, dense)
    assert made == sorted(_ATTENTION + rest)
    assert telemetry.gauge("remat.kept_values").value == len(made)
    assert telemetry.gauge("remat.kept_bytes").value == sum(
        math.prod(shape) * jnp.dtype(dtype).itemsize for shape, dtype in made)
    # a shorter list keeps less, and the dot path has no kernel's residuals
    short = (deepseek_v3.KEPT_LATENT, deepseek_v3.KEPT_ROPE_KEY)
    assert _made(short, dense, "dot") == sorted(_ATTENTION[1:3])
    # K and V, 4 heads of 24 + 16, are not among them
    assert not any(shape[-2:] in ((4, 24), (4, 32)) for shape, _ in made)


def test_the_four_families_share_the_mixtures_code_and_none_copies_it():
    # the expert layer's module is one class, and so are the share's checks
    for name in ("RoutedShare", "check_share", "balance_expert_bias",
                 "expert_loads", "sown_loads"):
        assert getattr(deepseek_v3, name) is getattr(nemotron_h, name) \
            is getattr(afmoe, name) is getattr(lfm2_moe, name) \
            is getattr(moe, name)
    assert deepseek_v3.make_optimizer is afmoe.make_optimizer \
        is moe.balanced_optimizer
    assert deepseek_v3.GatedMLP is afmoe.GatedMLP is moe.GatedMLP
    with open(deepseek_v3.__file__) as f:
        source = f.read()
    assert "def balance(" not in source and "routed_experts(" not in source
    assert "gmm(" not in source and "pallas_call" not in source
    # the checkpoint's policy is one definition too, and the shell's to apply
    assert decoder.keeping is keeping
    # the stack, the loss and the init are the shell's (models/decoder.py):
    # one object each under the five families' names, and no family embeds,
    # wraps its layers in a checkpoint, writes a next-token loss or wraps the
    # share in a module of its own
    families = (olmoe, afmoe, lfm2_moe, nemotron_h, deepseek_v3)
    for family in families:
        assert family.make_loss_fn is decoder.make_loss_fn
        assert family.init_params is decoder.init_params
        with open(family.__file__) as f:
            source = f.read()
        for copied in ("nn.Embed(", "nn.remat(", "log_softmax", "fused_lm_head_nll",
                       "jit_init", "sigmoid_topk_route"):
            assert copied not in source, (family.__name__, copied)
    models = (olmoe.Olmoe, afmoe.Afmoe, lfm2_moe.Lfm2Moe, nemotron_h.NemotronH,
              deepseek_v3.DeepseekV3)
    assert all(issubclass(model, decoder.Decoder) for model in models)
    assert [model.final_norm for model in models] == [
        "ln_f", "ln_f", "embedding_norm", "norm_f", "ln_f"]
    assert [model.tied for model in models] == [False, False, True, False, False]
    assert nemotron_h.NemotronH.kept is nemotron_h.KEPT
    assert deepseek_v3.DeepseekV3.kept is deepseek_v3.KEPT
    # the expert's form is an argument of the shared code, traced under its gauge
    cfg = deepseek_v3.DeepseekV3Config(dtype=jnp.float32, **TINY)
    layer = deepseek_v3.RoutedShare(cfg, cfg.d_expert * cfg.n_shared_experts)
    h = jnp.zeros((1, 8, 64))
    params = layer.init(jax.random.PRNGKey(0), h)["params"]
    layer.apply({"params": params}, h)
    assert telemetry.gauge("moe.expert_form").value == 3


def test_an_unknown_impl_or_share_is_refused():
    with pytest.raises(ValueError, match="Unknown attention_impl"):
        deepseek_v3.DeepseekV3Config(attention_impl="ring")
    with pytest.raises(ValueError, match="inside the router's width"):
        deepseek_v3.DeepseekV3Config(experts_held=8, first_expert_held=124)
    with pytest.raises(ValueError, match="n_dense_layers"):
        deepseek_v3.DeepseekV3Config(n_layers=2, n_dense_layers=3)
    with pytest.raises(ValueError, match="must be even"):
        deepseek_v3.DeepseekV3Config(qk_rope_head_dim=63)
    assert deepseek_v3.DeepseekV3Config().qk_head_dim == 192


def test_a_step_through_the_normal_path_moves_the_bias_by_the_rule():
    """``AutoDist(...)`` session and ``train()``, nothing on the side, with
    flash over a shared rotary key, the fused head and every layer
    recomputed, in bfloat16: after one optimizer step every expert-bias leaf
    has moved by ``coeff * (sign(mean c - c_e) - its mean)``; the other
    leaves moved by AdamW, latent attention's own among them; three steps more
    and the loss falls."""
    cfg = deepseek_v3.DeepseekV3Config(
        dtype=jnp.bfloat16, attention_impl="flash", fused_head=True, remat=True,
        load_balance_coeff=1e-3, **TINY)
    model, params = deepseek_v3.init_params(cfg)
    params = _stirred(params, scale=0.05)
    batch = deepseek_v3.synthetic_batch(cfg, batch_size=8, seq_len=32)
    loss_fn = deepseek_v3.make_loss_fn(model)
    optimizer = deepseek_v3.make_optimizer(1e-2, cfg.load_balance_coeff)
    grads = jax.jit(jax.grad(loss_fn))(
        params, {"tokens": jnp.asarray(batch["tokens"])})

    ad = AutoDist(strategy_builder=AllReduce())
    runner = ad.create_distributed_session(loss_fn, params, optimizer,
                                           example_batch=batch)
    losses = []

    def run(start, steps):      # one session, one compiled step, for both runs
        final = train(runner, start, iter([batch] * steps), steps=steps,
                      log_every=1,
                      on_metrics=lambda step, loss, rate: losses.append(float(loss)))
        return jax.device_get(final.params)

    after = run(params, 1)
    for block in ("block_1", "block_2"):
        load_error = np.asarray(grads[block]["moe"]["expert_bias"])
        assert np.abs(load_error).max() > 0
        signs = np.sign(load_error)        # sign(c_e - mean c)
        want = -cfg.load_balance_coeff * (signs - signs.mean())
        moved = np.asarray(after[block]["moe"]["expert_bias"]) \
            - np.asarray(params[block]["moe"]["expert_bias"])
        np.testing.assert_allclose(moved, want, atol=1e-7)
    for leaf in (("block_0", "attn", "kv_down", "kernel"),
                 ("block_0", "attn", "kv_norm", "scale"),
                 ("block_2", "attn", "kv_up", "kernel"),
                 ("block_1", "attn", "query", "kernel"),
                 ("block_0", "mlp", "gate", "kernel"), ("block_1", "moe", "up"),
                 ("block_1", "moe", "shared", "down", "kernel"),
                 ("lm_head", "kernel")):
        a, b = after, params
        for key in leaf:
            a, b = a[key], b[key]
        assert float(jnp.abs(a - b).max()) > 0, leaf
    run(after, 3)
    assert len(losses) >= 2 and all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_init_runs_the_plain_path_whatever_the_config_says():
    """Init sees a handful of positions: no kernel is compiled for them, and
    the parameters do not depend on the implementations chosen."""
    plain = deepseek_v3.DeepseekV3Config(**TINY)
    kernels = dataclasses.replace(plain, attention_impl="flash", fused_head=True,
                                  remat=True)
    a = deepseek_v3.init_params(plain, jax.random.PRNGKey(3))[1]
    b = deepseek_v3.init_params(kernels, jax.random.PRNGKey(3))[1]
    jax.tree_util.tree_map(np.testing.assert_array_equal, a, b)
    assert math.isfinite(float(jnp.abs(a["embed"]["embedding"]).max()))


def test_latent_attentions_scopes_sit_inside_the_layers_in_the_lowered_program():
    """The five named scopes reach every operation's ``op_name`` under the
    layer's own scope, which is where a device trace's reader finds them."""
    cfg = deepseek_v3.DeepseekV3Config(**TINY)
    model, params = deepseek_v3.init_params(cfg)
    text = jax.jit(deepseek_v3.make_loss_fn(model)).lower(
        params, _batch(cfg)).as_text(debug_info=True)
    for scope in ("mla.q_proj", "mla.kv_down", "mla.kv_up", "mla.rope",
                  "mla.out_proj"):
        assert f"block_2/attn/{scope}/" in text, scope
