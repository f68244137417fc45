"""LFM2-MoE (``models/lfm2_moe.py``): the system's loss and whole gradient
against the plain reference the benchmark checks it with on the chip
(``benchmark/reference/lfm2_moe.py``) for a stack with both layer kinds behind
a dense layer, the sigmoid router's top-4 weights by hand with the published
``1e-6``, one chip's share of the experts (the eight shares of a 64-wide
router add up to the uncut layer), the code the two sigmoid-routed families
share (``models/moe.py``; ``afmoe`` still builds the tree it built) and a step
through the normal path. Tiny widths on the CPU mesh; kernels in interpret
mode."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import AutoDist, train
from autodist_tpu.models import afmoe, lfm2_moe, moe
from autodist_tpu.strategy import AllReduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tests import reference_programs  # noqa: E402

# Both layer kinds behind a dense conv layer, 2 query heads a KV head, the
# share: experts 2-3 of 8, top-2; d a multiple of 128 for the conv kernels.
TINY = dict(vocab_size=256, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
            layer_types=(lfm2_moe.CONV, lfm2_moe.FULL, lfm2_moe.CONV),
            n_dense_layers=1, d_ff=96, d_expert=32, n_experts_routed=8,
            experts_held=2, first_expert_held=2, top_k=2, max_len=64)


def _rel_l2(a, b):
    leaves = lambda t: jax.tree_util.tree_leaves(t)  # noqa: E731
    num = sum(float(jnp.sum(jnp.square(x - y))) for x, y in zip(leaves(a), leaves(b)))
    return (num / sum(float(jnp.sum(jnp.square(y))) for y in leaves(b))) ** 0.5


def _reference_kwargs(cfg):
    return dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim, layer_types=cfg.layer_types,
                n_dense_layers=cfg.n_dense_layers, top_k=cfg.top_k,
                rms_eps=cfg.rms_eps, rope_theta=cfg.rope_theta,
                route_norm=cfg.route_norm, route_scale=cfg.route_scale,
                route_eps=cfg.route_eps,
                first_expert_held=cfg.first_expert_held)


def _with_bias(params, scale=0.05):
    """Every ``expert_bias`` leaf drawn, not zero: large enough to change
    choices."""
    def draw(path, x):
        if path[-1].key != "expert_bias":
            return x
        return scale * jax.random.normal(jax.random.PRNGKey(5), x.shape)
    return jax.tree_util.tree_map_with_path(draw, params)


# The tolerances are OLMoE's and AFMoE's, for their reasons: float32
# activations agree to rounding, bfloat16 to parts in a thousand of the loss
# and a few percent of the gradient; a dropped term moves either by far more.
@pytest.mark.parametrize("dtype,attention,conv,fused,loss_tol,grad_tol", [
    (jnp.float32, "dot", "xla", False, 1e-5, 1e-5),
    (jnp.float32, "flash", "pallas", True, 1e-5, 1e-5),
    (jnp.bfloat16, "flash", "pallas", True, 1e-3, 3e-2),
], ids=["f32-xla", "f32-kernels", "bf16-kernels"])
def test_loss_and_gradients_match_the_plain_reference(dtype, attention, conv,
                                                      fused, loss_tol, grad_tol):
    cfg = lfm2_moe.Lfm2MoeConfig(dtype=dtype, attention_impl=attention,
                                 conv_impl=conv, fused_head=fused, **TINY)
    model, params = lfm2_moe.init_params(cfg, jax.random.PRNGKey(1))
    params = _with_bias(params)
    batch = {"tokens": jnp.asarray(
        lfm2_moe.synthetic_batch(cfg, 2, 32, seed=3)["tokens"])}
    loss, grads = jax.jit(jax.value_and_grad(lfm2_moe.make_loss_fn(model)))(
        params, batch)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = reference_programs.value_and_grad(
            "lfm2_moe", **_reference_kwargs(cfg))(params, batch)
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) <= loss_tol
    assert _rel_l2(grads, ref_grads) <= grad_tol
    assert {str(g.dtype) for g in jax.tree_util.tree_leaves(grads)} == {"float32"}
    # every leaf takes a gradient, the taps and the tied table among them
    assert float(jnp.abs(grads["block_0"]["conv"]["conv"]).max()) > 0
    assert float(jnp.abs(grads["embed"]["embedding"]).max()) > 0
    d_bias = grads["block_1"]["moe"]["expert_bias"]
    assert abs(float(d_bias.sum())) < 1e-6 and float(jnp.abs(d_bias).max()) > 0


def test_the_tiny_stack_has_the_parameters_the_equations_name():
    cfg = lfm2_moe.Lfm2MoeConfig(**TINY)
    _, params = lfm2_moe.init_params(cfg)
    d, wide, narrow = 128, 4 * 32, 2 * 32
    conv = d * 3 * d + d * 3 + d * d                      # in_proj, taps, out_proj
    attention = 2 * d * wide + 2 * d * narrow + 2 * 32    # q, o; k, v; QK norms
    routed = d * 8 + 8 + 2 * 3 * d * 32                   # router, bias, 2 experts
    want = (conv + 2 * d + 3 * d * 96) + (attention + 2 * d + routed) \
        + (conv + 2 * d + routed) + 256 * d + d           # the tied table once
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == want
    assert set(params) == {"block_0", "block_1", "block_2", "embed",
                           "embedding_norm"}
    assert set(params["block_0"]) == {"conv", "operator_norm", "ffn_norm", "mlp"}
    assert set(params["block_1"]) == {"attn", "operator_norm", "ffn_norm", "moe"}
    assert set(params["block_0"]["conv"]) == {"in_proj", "conv", "out_proj"}
    assert params["block_0"]["conv"]["conv"].shape == (d, 3)
    assert set(params["block_2"]["moe"]) == {"router", "expert_bias", "gate",
                                             "up", "down"}     # no shared expert
    assert params["block_2"]["moe"]["gate"].shape == (2, d, 32)
    # the published sizes are the defaults
    full = lfm2_moe.Lfm2MoeConfig()
    assert (full.n_layers, full.layer_types.count(lfm2_moe.FULL), full.d_model,
            full.n_heads, full.n_kv_heads, full.head_dim, full.d_ff,
            full.d_expert, full.n_experts_routed, full.top_k, full.conv_kernel,
            full.vocab_size) == (40, 10, 2048, 32, 8, 64, 11776, 1536, 64, 4, 3,
                                 65536)
    assert full.layer_types[:8] == ("conv", "conv", "full_attention", "conv") * 2


def test_top_4_under_a_bias_that_changes_the_choice_and_not_the_weight():
    scores = jnp.asarray([[0.9, 0.1, 0.5, 0.6, 0.2, 0.3, 0.8, 0.4]])
    bias = jnp.asarray([-0.6, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.45])
    lfm2 = dict(route_norm=True, route_scale=1.0, route_eps=1e-6)
    route = moe.sigmoid_topk_route(scores, 4, bias, **lfm2)
    # chosen by score + bias: 7 (0.85), 6 (0.8), 3 (0.6), 2 (0.5); expert 0
    # falls to 0.3. The weights are the scores WITHOUT the bias, over their
    # sum + 1e-6.
    np.testing.assert_array_equal(route.indices, [[7, 6, 3, 2]])
    total = 0.4 + 0.8 + 0.6 + 0.5
    np.testing.assert_allclose(
        route.weights, [[s / (total + 1e-6) for s in (0.4, 0.8, 0.6, 0.5)]],
        rtol=1e-6)
    plain = moe.sigmoid_topk_route(scores, 4, None, **lfm2)
    np.testing.assert_array_equal(plain.indices, [[0, 6, 3, 2]])
    # the bias steers the choice and takes no gradient through it
    d_bias = jax.grad(lambda b: moe.sigmoid_topk_route(
        scores, 4, b, **lfm2).weights[0, 0])(bias)
    np.testing.assert_array_equal(d_bias, np.zeros(8))


@pytest.mark.parametrize("eps,total", [(1e-6, 4e-6 + 1e-6), (1e-20, 4e-6)],
                         ids=["lfm2-1e-6", "afmoe-default-1e-20"])
def test_the_normalisers_epsilon_is_the_familys(eps, total):
    """Where the chosen scores are tiny the published 1e-6 shows: four scores
    of 1e-6 weigh 1/5 each under LFM2's normaliser and 1/4 under AFMoE's, whose
    1e-20 stays the default."""
    scores = jnp.full((1, 8), 1e-6).at[0, 4:].set(1e-7)
    kwargs = {} if eps == 1e-20 else {"route_eps": eps}
    route = moe.sigmoid_topk_route(scores, 4, None, **kwargs)
    np.testing.assert_allclose(route.weights, np.full((1, 4), 1e-6 / total),
                               rtol=1e-5)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """What the guide asks of a share: the routed parts that the shares
    ``first_expert_held`` = 0, 8, ..., 56 of a 64-wide router give add up to
    what the uncut reference gives for the whole layer (there is no shared
    expert to count once). The system's layer module on each share's slice of
    one parameter tree; the reference on the whole tree."""
    from benchmark.reference import lfm2_moe as reference
    wide = dict(TINY, d_model=32, d_expert=16, n_experts_routed=64, top_k=4)
    cfg = lfm2_moe.Lfm2MoeConfig(dtype=jnp.float32, **dict(
        wide, experts_held=64, first_expert_held=0))
    d, tokens = cfg.d_model, 40
    whole = lfm2_moe.RoutedShare(cfg).init(
        jax.random.PRNGKey(2), jnp.zeros((1, 4, d)))["params"]
    whole = _with_bias(whole, scale=0.2)
    h = jax.random.normal(jax.random.PRNGKey(3), (1, tokens, d))

    total, loads = 0.0, []
    for first in range(0, 64, 8):
        share_cfg = lfm2_moe.Lfm2MoeConfig(dtype=jnp.float32, **dict(
            wide, experts_held=8, first_expert_held=first, rows_bound=24))
        params = dict(whole, **{name: whole[name][first:first + 8]
                                for name in ("gate", "up", "down")})
        (out, _), sown = lfm2_moe.RoutedShare(share_cfg).apply(
            {"params": params}, h, mutable=["intermediates"])
        total = total + out
        loads.append(sown["intermediates"]["load"][0])
    with jax.default_matmul_precision("highest"):
        routed, _ = reference.mixture(
            h.reshape(tokens, d), whole, top_k=cfg.top_k,
            route_norm=cfg.route_norm, route_scale=cfg.route_scale,
            route_eps=cfg.route_eps, first_expert_held=0)
    np.testing.assert_allclose(total, routed.reshape(1, tokens, d),
                               rtol=1e-4, atol=1e-5)
    # every rank makes the same choice over the whole width
    for load in loads:
        np.testing.assert_array_equal(load, loads[0])
    assert float(loads[0].sum()) == tokens * 4
    # and the whole bank in one layer is the same uncut result
    uncut, _ = lfm2_moe.RoutedShare(cfg).apply({"params": whole}, h)
    np.testing.assert_allclose(uncut, routed.reshape(1, tokens, d),
                               rtol=1e-4, atol=1e-5)


def test_a_layer_whose_held_rows_take_three_passes_equals_the_one_pass_layer():
    """``lfm2_moe``'s expert layer, experts 8-15 of 64 held, top-4 with the
    1e-6: under a bound the held rows fill three times (pass 0 kept, two
    recomputed) the output and the gradients of the input and of every
    parameter are those of the same layer in one pass over all ``T*k`` rows,
    and the layer sows the three."""
    wide = dict(TINY, d_model=32, d_expert=16, n_experts_routed=64, top_k=4,
                experts_held=8, first_expert_held=8)
    layers = {bound: lfm2_moe.RoutedShare(lfm2_moe.Lfm2MoeConfig(
        dtype=jnp.float32, rows_bound=bound, **wide)) for bound in (None, 48)}
    params = layers[None].init(jax.random.PRNGKey(2),
                               jnp.zeros((1, 4, 32)))["params"]
    # three held experts preferred: most tokens choose them
    params = dict(params, expert_bias=jnp.zeros(64).at[8:11].set(0.5))
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 32))
    target = jax.random.normal(jax.random.PRNGKey(4), h.shape)

    def run(bound):
        def loss(params, h):
            (y, bias_term), sown = layers[bound].apply(
                {"params": params}, h, mutable=["intermediates"])
            return (y * target).sum() + bias_term, (y, sown["intermediates"])
        (_, (y, sown)), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(params, h)
        return y, grads, sown

    y, grads, sown = run(48)
    want_y, want_grads, want_sown = run(None)
    held = int(sown["load"][0][8:16].sum())
    assert 96 < held <= 144 < 160 and int(sown["passes"][0]) == 3
    assert int(want_sown["passes"][0]) == 1
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-6)
    for g, r in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-6)


def test_the_two_families_share_the_mixtures_code_and_neither_copies_it():
    for name in ("GatedMLP", "RoutedShare", "check_share", "balance_expert_bias",
                 "expert_loads", "sown_loads"):
        assert getattr(afmoe, name) is getattr(lfm2_moe, name) is getattr(moe, name)
    assert afmoe.make_optimizer is lfm2_moe.make_optimizer is moe.balanced_optimizer
    for module in (afmoe, lfm2_moe):
        with open(module.__file__) as f:
            source = f.read()
        assert "def balance(" not in source and "routed_experts(" not in source


def test_afmoe_still_builds_the_parameter_tree_it_built():
    """Moving the routed share into ``models/moe.py`` left Trinity's tree as
    it was: the same names, shapes and (from one key) values' sums, so its
    compiled step is the parent's."""
    cfg = afmoe.AfmoeConfig(
        vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        layer_types=(afmoe.SLIDING, afmoe.FULL), n_dense_layers=1, d_ff=96,
        d_expert=32, n_experts_routed=8, experts_held=2, first_expert_held=2,
        top_k=2, window=8, max_len=64)
    _, params = afmoe.init_params(cfg, jax.random.PRNGKey(0))
    paths = {"/".join(p.key for p in path): tuple(x.shape)
             for path, x in jax.tree_util.tree_leaves_with_path(params)}
    attn = {"attn/gate/kernel": (64, 64), "attn/key/kernel": (64, 32),
            "attn/k_norm/scale": (16,), "attn/out/kernel": (64, 64),
            "attn/q_norm/scale": (16,), "attn/query/kernel": (64, 64),
            "attn/value/kernel": (64, 32), "ln_in/scale": (64,),
            "ln_post_attn/scale": (64,), "ln_post_mlp/scale": (64,),
            "ln_pre_mlp/scale": (64,)}
    want = {"embed/embedding": (256, 64), "lm_head/kernel": (64, 256),
            "ln_f/scale": (64,)}
    want.update({f"block_0/{k}": v for k, v in attn.items()})
    want.update({f"block_1/{k}": v for k, v in attn.items()})
    want.update({f"block_0/mlp/{m}/kernel": s for m, s in
                 (("gate", (64, 96)), ("up", (64, 96)), ("down", (96, 64)))})
    want.update({"block_1/moe/router": (64, 8), "block_1/moe/expert_bias": (8,),
                 "block_1/moe/gate": (2, 64, 32), "block_1/moe/up": (2, 64, 32),
                 "block_1/moe/down": (2, 32, 64),
                 "block_1/moe/shared/gate/kernel": (64, 32),
                 "block_1/moe/shared/up/kernel": (64, 32),
                 "block_1/moe/shared/down/kernel": (32, 64)})
    assert paths == want
    # the parent's values from this key (its init read on the parent's tree)
    moe_leaves = params["block_1"]["moe"]
    sums = {name: float(jnp.abs(moe_leaves[name]).sum())
            for name in ("router", "gate", "up", "down")}
    sums["shared"] = float(jnp.abs(moe_leaves["shared"]["up"]["kernel"]).sum())
    np.testing.assert_allclose(
        [sums[k] for k in ("router", "gate", "up", "down", "shared")],
        PARENT_SUMS, rtol=1e-6)
    assert not np.asarray(moe_leaves["expert_bias"]).any()


# sum |x| of block_1/moe's router, gate, up, down and shared/up/kernel as the
# parent commit (82e25d1) initialises them from PRNGKey(0) at this size
PARENT_SUMS = (8.470870971679688, 65.36653137207031, 64.41575622558594,
               64.51224517822266, 32.630279541015625)


def test_an_unknown_conv_impl_or_layer_kind_is_refused():
    with pytest.raises(ValueError, match="Unknown conv_impl"):
        lfm2_moe.Lfm2MoeConfig(conv_impl="mosaic")
    with pytest.raises(ValueError, match="layer_types must be of"):
        lfm2_moe.Lfm2MoeConfig(layer_types=("conv", "sliding_attention"))
    with pytest.raises(ValueError, match="inside the router's width"):
        lfm2_moe.Lfm2MoeConfig(experts_held=8, first_expert_held=60)


def test_a_step_through_the_normal_path_moves_the_bias_by_the_rule():
    """``AutoDist(...)`` session and ``train()``, nothing on the side, with
    the conv kernels, flash and the fused tied head in bfloat16: after one
    optimizer step every expert-bias leaf has moved by ``coeff * (sign(mean c
    - c_e) - its mean)``; the other leaves moved by AdamW, the taps among
    them; three steps and the loss falls."""
    cfg = lfm2_moe.Lfm2MoeConfig(dtype=jnp.bfloat16, attention_impl="flash",
                                 conv_impl="pallas", fused_head=True,
                                 load_balance_coeff=1e-3, **TINY)
    model, params = lfm2_moe.init_params(cfg)
    params = _with_bias(params)
    batch = lfm2_moe.synthetic_batch(cfg, batch_size=8, seq_len=32)
    loss_fn = lfm2_moe.make_loss_fn(model)
    optimizer = lfm2_moe.make_optimizer(1e-2, cfg.load_balance_coeff)
    grads = jax.jit(jax.grad(loss_fn))(
        params, {"tokens": jnp.asarray(batch["tokens"])})
    ad = AutoDist(strategy_builder=AllReduce())
    runner = ad.create_distributed_session(loss_fn, params, optimizer,
                                           example_batch=batch)

    def one_run(steps):     # one session, one compiled step, for both runs
        losses = []
        final = train(runner, params, iter([batch] * steps), steps=steps,
                      log_every=1,
                      on_metrics=lambda step, loss, rate: losses.append(float(loss)))
        return jax.device_get(final.params), losses

    after, _ = one_run(1)
    for block in ("block_1", "block_2"):
        load_error = np.asarray(grads[block]["moe"]["expert_bias"])
        assert np.abs(load_error).max() > 0
        signs = np.sign(load_error)        # sign(c_e - mean c)
        want = -cfg.load_balance_coeff * (signs - signs.mean())
        moved = np.asarray(after[block]["moe"]["expert_bias"]) \
            - np.asarray(params[block]["moe"]["expert_bias"])
        np.testing.assert_allclose(moved, want, atol=1e-7)
    for leaf in (("block_0", "conv", "conv"), ("block_0", "conv", "in_proj", "kernel"),
                 ("embed", "embedding")):
        a, b = after, params
        for key in leaf:
            a, b = a[key], b[key]
        assert float(jnp.abs(a - b).max()) > 0, leaf
    _, losses = one_run(3)
    assert len(losses) >= 2 and all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_init_runs_the_plain_path_whatever_the_config_says():
    """Init sees a handful of positions: no kernel is compiled for them, and
    the parameters do not depend on the implementations chosen."""
    plain = lfm2_moe.Lfm2MoeConfig(**TINY)
    kernels = dataclasses.replace(plain, conv_impl="pallas",
                                  attention_impl="flash", fused_head=True)
    a = lfm2_moe.init_params(plain, jax.random.PRNGKey(3))[1]
    b = lfm2_moe.init_params(kernels, jax.random.PRNGKey(3))[1]
    jax.tree_util.tree_map(np.testing.assert_array_equal, a, b)
