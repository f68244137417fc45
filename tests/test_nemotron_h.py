"""Nemotron-H (``models/nemotron_h.py``): the system's loss and whole gradient
against the plain reference the benchmark checks it with on the chip
(``benchmark/reference/nemotron_h.py``) for the cell's own pattern
``MEMEM*EME``, the parameter tree the equations name, the gated grouped norm
and the initialisation by hand, one chip's share of the ``relu2`` experts (the
16 shares of a 128-wide router and the shared expert once add up to the uncut
layer), per-layer recomputation, the code the three sigmoid-routed families
share (``models/moe.py``) and a step through the normal path. Tiny widths on
the CPU mesh; kernels in interpret mode."""

import collections
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
from autodist_tpu.models import afmoe, common, lfm2_moe, moe, nemotron_h
from autodist_tpu.strategy import AllReduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# The cell's pattern; 2 heads a group and 2 query heads a KV head; the share:
# experts 2-4 of 8, top-3; a state and a group's heads x head_dim of 128 lanes
# and chunks of 128 for the scan's kernels.
TINY = dict(vocab_size=256, d_model=64, pattern="MEMEM*EME", mamba_heads=4,
            mamba_head_dim=64, n_groups=2, d_state=128, conv_kernel=4, chunk=128,
            n_heads=4, n_kv_heads=2, head_dim=16, d_expert=24, d_shared=40,
            n_experts_routed=8, experts_held=3, first_expert_held=2, top_k=3,
            max_len=64)


def _share(cfg):
    """The expert mixer's module as ``nemotron_h.NemotronHBlock`` builds it."""
    return nemotron_h.RoutedShare(cfg, cfg.d_shared, "relu2")


def _rel_l2(a, b):
    leaves = lambda t: jax.tree_util.tree_leaves(t)  # noqa: E731
    num = sum(float(jnp.sum(jnp.square(x - y))) for x, y in zip(leaves(a), leaves(b)))
    return (num / sum(float(jnp.sum(jnp.square(y))) for y in leaves(b))) ** 0.5


def _reference_kwargs(cfg):
    return dict(pattern=cfg.pattern, mamba_heads=cfg.mamba_heads,
                mamba_head_dim=cfg.mamba_head_dim, n_groups=cfg.n_groups,
                d_state=cfg.d_state, n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                top_k=cfg.top_k, rms_eps=cfg.rms_eps, route_norm=cfg.route_norm,
                route_scale=cfg.route_scale, route_eps=cfg.route_eps,
                first_expert_held=cfg.first_expert_held)


def _stirred(params, scale=0.2):
    """The leaves that init sets to constants (zeros, ones, a ramp), drawn:
    an ``expert_bias`` large enough to change choices, a ``D``, a norm weight
    and a convolution bias that a dropped factor would show in."""
    def draw(path, x):
        if path[-1].key not in ("expert_bias", "D", "A_log", "norm", "scale",
                                "conv_bias"):
            return x
        key = jax.random.PRNGKey(sum(map(ord, jax.tree_util.keystr(path))))
        return x + scale * jax.random.normal(key, x.shape)
    return jax.tree_util.tree_map_with_path(draw, params)


# The tolerances are OLMoE's, AFMoE's and LFM2's, for their reasons: float32
# activations agree to rounding, bfloat16 to parts in a thousand of the loss
# and a few percent of the gradient; a dropped term moves either by far more.
@pytest.mark.parametrize("dtype,attention,ssm,fused,remat,exact,loss_tol,grad_tol", [
    (jnp.float32, "dot", "xla", False, False, False, 1e-5, 2e-5),
    (jnp.float32, "flash", "pallas", True, True, False, 1e-5, 2e-5),
    (jnp.bfloat16, "flash", "pallas", True, True, False, 2e-3, 4e-2),
    # the cell's: layer 0 in float32 around a scan on bfloat16 operands
    (jnp.bfloat16, "flash", "pallas", True, True, True, 2e-3, 4e-2),
    (jnp.float32, "dot", "xla", False, False, True, 1e-5, 2e-5),
], ids=["f32-xla", "f32-kernels-remat", "bf16-kernels-remat",
        "bf16-kernels-remat-exact-first-layer", "f32-xla-exact-first-layer"])
def test_loss_and_gradients_match_the_plain_reference(dtype, attention, ssm,
                                                      fused, remat, exact,
                                                      loss_tol, grad_tol):
    from benchmark.reference import nemotron_h as reference
    cfg = nemotron_h.NemotronHConfig(dtype=dtype, attention_impl=attention,
                                     ssm_impl=ssm, fused_head=fused, remat=remat,
                                     exact_first_layer=exact, rows_bound=40,
                                     **TINY)
    model, params = nemotron_h.init_params(cfg, jax.random.PRNGKey(1))
    params = _stirred(params)
    batch = {"tokens": jnp.asarray(
        nemotron_h.synthetic_batch(cfg, 2, 40, seed=3)["tokens"])}
    loss, grads = jax.jit(jax.value_and_grad(nemotron_h.make_loss_fn(model)))(
        params, batch)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p, b: reference.loss(p, b, **_reference_kwargs(cfg))))(
                params, batch)
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) <= loss_tol
    assert _rel_l2(grads, ref_grads) <= grad_tol
    assert {str(g.dtype) for g in jax.tree_util.tree_leaves(grads)} == {"float32"}
    # every leaf takes a gradient: the scan's A, D and dt_bias, the taps and
    # their bias among them
    for leaf in ("A_log", "D", "dt_bias", "conv", "conv_bias", "norm"):
        assert float(jnp.abs(grads["block_0"]["mamba"][leaf]).max()) > 0, leaf
    d_bias = grads["block_1"]["moe"]["expert_bias"]
    assert abs(float(d_bias.sum())) < 1e-6 and float(jnp.abs(d_bias).max()) > 0


def test_the_tiny_stack_has_the_parameters_the_equations_name():
    cfg = nemotron_h.NemotronHConfig(**TINY)
    _, params = nemotron_h.init_params(cfg)
    d, d_inner, conv_dim = 64, 4 * 64, 4 * 64 + 2 * 2 * 128
    mamba = (d * (d_inner + conv_dim + 4) + conv_dim * 4 + conv_dim + 3 * 4
             + d_inner + d_inner * d)
    attention = 2 * d * 4 * 16 + 2 * d * 2 * 16
    experts = d * 8 + 8 + 3 * 2 * d * 24 + 2 * d * 40    # router, bias, 3 held, shared
    want = 4 * (mamba + d) + (attention + d) + 4 * (experts + d) \
        + 2 * 256 * d + d                                 # embedding and untied head
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == want
    assert set(params) == {f"block_{i}" for i in range(9)} | {
        "embed", "norm_f", "lm_head"}
    for i, kind in enumerate("MEMEM*EME"):
        mixer = {"M": "mamba", "E": "moe", "*": "attn"}[kind]
        assert set(params[f"block_{i}"]) == {"norm", mixer}      # one mixer a layer
    assert set(params["block_0"]["mamba"]) == {
        "in_proj", "conv", "conv_bias", "A_log", "D", "dt_bias", "norm", "out_proj"}
    assert params["block_0"]["mamba"]["conv"].shape == (conv_dim, 4)
    assert set(params["block_1"]["moe"]) == {"router", "expert_bias", "up",
                                             "down", "shared"}   # relu2: no gate
    assert set(params["block_1"]["moe"]["shared"]) == {"up", "down"}
    assert params["block_1"]["moe"]["up"].shape == (3, d, 24)
    assert set(params["block_5"]["attn"]) == {"query", "key", "value", "out"}
    # the published sizes are the defaults
    full = nemotron_h.NemotronHConfig()
    assert (full.n_layers, [full.pattern.count(k) for k in "ME*"], full.d_model,
            full.mamba_heads, full.mamba_head_dim, full.n_groups, full.d_state,
            full.conv_kernel, full.chunk, full.d_inner, full.n_heads,
            full.n_kv_heads, full.head_dim, full.d_expert, full.d_shared,
            full.n_experts_routed, full.top_k, full.route_scale,
            full.vocab_size) == (52, [23, 23, 6], 2688, 64, 64, 8, 128, 4, 128,
                                 4096, 32, 2, 128, 1856, 3712, 128, 6, 2.5,
                                 131072)
    assert full.pattern.startswith("MEMEM*EME")


def test_the_initialisation_is_the_mamba_2_references():
    cfg = nemotron_h.NemotronHConfig(**TINY)
    _, params = nemotron_h.init_params(cfg, jax.random.PRNGKey(4))
    mamba = params["block_0"]["mamba"]
    np.testing.assert_allclose(mamba["A_log"], np.log([1, 2, 3, 4]), rtol=1e-6)
    np.testing.assert_array_equal(mamba["D"], np.ones(4))
    dt = np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    assert ((dt >= cfg.time_step_min * 0.999) & (dt <= cfg.time_step_max * 1.001)).all()
    assert float(jnp.abs(mamba["conv"]).max()) <= 0.5 and \
        float(jnp.abs(mamba["conv_bias"]).max()) <= 0.5
    # a mixer's output matrix is rescaled by sqrt(n_layers), its input is not
    narrow = float(jnp.std(mamba["out_proj"]["kernel"]))
    assert narrow == pytest.approx(0.02 / 3, rel=0.1)
    assert float(jnp.std(mamba["in_proj"]["kernel"])) == pytest.approx(0.02, rel=0.1)
    assert float(jnp.std(params["block_5"]["attn"]["out"]["kernel"])) == \
        pytest.approx(0.02 / 3, rel=0.15)
    _, plain = nemotron_h.init_params(dataclasses.replace(
        cfg, rescale_prenorm_residual=False), jax.random.PRNGKey(4))
    assert float(jnp.std(plain["block_0"]["mamba"]["out_proj"]["kernel"])) == \
        pytest.approx(0.02, rel=0.1)


def test_the_gated_norm_is_a_mean_square_over_each_groups_run():
    y = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 12))
    z = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 12))
    scale = jnp.arange(1.0, 13.0)
    got = nemotron_h.gated_group_norm(y, z, scale, groups=3, eps=1e-5)
    gated = np.asarray(y * z / (1 + np.exp(-np.asarray(z))))
    want = np.empty_like(gated)
    for g in range(3):
        run = gated[..., 4 * g:4 * g + 4]
        want[..., 4 * g:4 * g + 4] = run / np.sqrt(
            (run ** 2).mean(axis=-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(got, want * np.asarray(scale), rtol=1e-5, atol=1e-6)


def test_the_sixteen_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """What the guide asks of a share: the routed parts that the shares
    ``first_expert_held`` = 0, 8, ..., 120 of a 128-wide router give, with the
    shared expert (which every chip computes alike) counted once, add up to
    what the uncut reference gives for the whole layer. The system's layer
    module on each share's slice of one parameter tree; the reference on the
    whole tree."""
    from benchmark.reference import nemotron_h as reference
    wide = dict(TINY, d_model=32, d_expert=16, d_shared=24, n_experts_routed=128,
                top_k=6)
    cfg = nemotron_h.NemotronHConfig(dtype=jnp.float32, **dict(
        wide, experts_held=128, first_expert_held=0))
    d, tokens = cfg.d_model, 40
    whole = _share(cfg).init(
        jax.random.PRNGKey(2), jnp.zeros((1, 4, d)))["params"]
    whole = _stirred(whole)
    h = jax.random.normal(jax.random.PRNGKey(3), (1, tokens, d))
    shared = moe.PlainMLP(cfg.d_shared, jnp.float32).apply(
        {"params": whole["shared"]}, h)

    routed_total, loads = 0.0, []
    for first in range(0, 128, 8):
        share_cfg = nemotron_h.NemotronHConfig(dtype=jnp.float32, **dict(
            wide, experts_held=8, first_expert_held=first, rows_bound=24))
        params = dict(whole, **{name: whole[name][first:first + 8]
                                for name in ("up", "down")})
        (out, _), sown = _share(share_cfg).apply(
            {"params": params}, h, mutable=["intermediates"])
        routed_total = routed_total + (out - shared)
        loads.append(sown["intermediates"]["load"][0])
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.mixture(
            h.reshape(tokens, d), whole, top_k=cfg.top_k,
            route_norm=cfg.route_norm, route_scale=cfg.route_scale,
            route_eps=cfg.route_eps, first_expert_held=0)
    np.testing.assert_allclose(shared + routed_total, uncut.reshape(1, tokens, d),
                               rtol=1e-4, atol=1e-5)
    # every rank makes the same choice over the whole width
    for load in loads:
        np.testing.assert_array_equal(load, loads[0])
    assert float(loads[0].sum()) == tokens * 6
    # and the whole bank in one layer is the same uncut result
    one, _ = _share(cfg).apply({"params": whole}, h)
    np.testing.assert_allclose(one, uncut.reshape(1, tokens, d),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dtype,kernels,rtol,atol", [
    (jnp.float32, False, 1e-5, 1e-7),
    # the kept values are the values a second forward would make: bfloat16
    # through the kernels agrees as float32 does (the tolerance is XLA's, which
    # fuses the two programs differently, not bfloat16's)
    (jnp.bfloat16, True, 1e-5, 1e-7),
], ids=["f32-xla", "bf16-kernels"])
def test_recomputing_every_layer_changes_no_number(dtype, kernels, rtol, atol):
    cfg = nemotron_h.NemotronHConfig(
        dtype=dtype, rows_bound=40, exact_first_layer=kernels,
        **(dict(attention_impl="flash", ssm_impl="pallas") if kernels else {}),
        **TINY)
    model, params = nemotron_h.init_params(cfg, jax.random.PRNGKey(1))
    params = _stirred(params)
    batch = {"tokens": jnp.asarray(
        nemotron_h.synthetic_batch(cfg, 2, 24, seed=5)["tokens"])}
    plain = jax.jit(jax.value_and_grad(nemotron_h.make_loss_fn(model)))(
        params, batch)
    again = jax.jit(jax.value_and_grad(nemotron_h.make_loss_fn(
        nemotron_h.NemotronH(dataclasses.replace(cfg, remat=True)))))(params, batch)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=rtol, atol=atol),
        plain, again)
    # and the layer's loads and passes are sown under it as without it
    _, sown = nemotron_h.NemotronH(dataclasses.replace(cfg, remat=True)).apply(
        {"params": params}, batch["tokens"][:, :-1], return_hidden=True,
        mutable=["intermediates"])
    loads = nemotron_h.sown_loads(sown["intermediates"])
    assert loads.shape == (4, 8) and float(loads.sum()) == 4 * 2 * 24 * 3
    assert moe.sown_passes(sown["intermediates"]).shape == (4,)


def _kernel_calls(jaxpr, counts=None):
    """Pallas calls by kernel name, sub-programs included."""
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            counts[str(eqn.params["name"])] += 1
            continue
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (tuple, list)) else [param]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _kernel_calls(sub, counts)
    return counts


@pytest.fixture(scope="module")
def gradient_programs():
    """``{remat: (kernel calls of jax.grad(loss) by name, the remat.* gauges
    its trace left)}`` of the cell's own settings at the tiny widths."""
    found = {}
    for remat in (False, True):
        telemetry.registry().clear()
        cfg = nemotron_h.NemotronHConfig(
            attention_impl="flash", ssm_impl="pallas", remat=remat,
            exact_first_layer=True, rows_bound=40, **TINY)
        model, params = nemotron_h.init_params(cfg, jax.random.PRNGKey(1))
        batch = {"tokens": jnp.asarray(
            nemotron_h.synthetic_batch(cfg, 2, 40, seed=3)["tokens"])}
        program = jax.make_jaxpr(jax.grad(nemotron_h.make_loss_fn(model)))(
            params, batch)
        found[remat] = (_kernel_calls(program.jaxpr),
                        {k: v for k, v in telemetry.snapshot().items()
                         if k.startswith("remat.")})
    return found


@pytest.mark.parametrize("kernel,calls,under_remat", [
    ("flash_fwd", 1, 1), ("flash_bwd_dkv", 1, 1), ("ssd_fwd", 4, 4),
    ("ssd_bwd", 4, 4), ("conv_silu_fwd", 4, 8), ("conv_silu_bwd", 4, 4),
    ("moe_gmm_fwd", 24, 24)])
def test_a_checkpointed_layer_runs_each_kept_forward_kernel_once(
        gradient_programs, kernel, calls, under_remat):
    """Four Mamba-2 layers and one attention layer: under ``remat`` the
    policy keeps what flash's and the scan's forward rules hand their
    backward, so the gradient program launches those forward kernels once a
    layer, as without ``remat`` (a bare ``jax.checkpoint`` launched each
    twice), and pass 0 of the routed share likewise (its two forward products
    ran again: 32); the convolution's output is not on the list and its
    forward kernel runs again."""
    assert gradient_programs[False][0][kernel] == calls
    assert gradient_programs[True][0][kernel] == under_remat


# What a layer of each kind keeps at the tiny widths, 2 sequences of 40: a
# Mamba-2 layer its [z | xBC | dt], the scan's y (whole chunks of 128) and one
# [64, 128] state a chunk and head; an expert layer the shared expert's up
# product, the router's logits and what pass 0 over the 40 rows of the bound
# makes for its transpose (the gathered rows, the up product, its relu and its
# mask, relu2, the down product that the weights' gradient reads, the rows'
# weights and their indices); attention q, k, v, flash's output and its
# log-sum-exp.
KEPT_BY_KIND = {
    nemotron_h.MAMBA: [((2, 40, 1028), "bfloat16"), ((2, 128, 4, 64), "bfloat16"),
                       ((2, 1, 2, 2, 64, 128), "float32")],
    nemotron_h.EXPERTS: [((2, 40, 40), "bfloat16"), ((80, 8), "float32"),
                         ((40, 64), "bfloat16"), ((40, 24), "bfloat16"),
                         ((40, 24), "bfloat16"), ((40, 24), "bool"),
                         ((40, 24), "bfloat16"), ((40, 64), "bfloat16"),
                         ((40,), "float32"), ((40,), "float32"), ((40,), "bool"),
                         ((40,), "int32"), ((40,), "int32"), ((40, 1), "int32"),
                         ((3,), "int32"), ((2,), "int32"), ((), "int32")],
    nemotron_h.ATTENTION: [((2, 40, 64), "bfloat16"), ((2, 40, 32), "bfloat16"),
                           ((2, 40, 32), "bfloat16"), ((2, 40, 4, 16), "bfloat16"),
                           ((8, 1, 40), "float32")],
}


def _bytes(kept):
    return sum(math.prod(shape) * jnp.dtype(dtype).itemsize for shape, dtype in kept)


def test_the_kept_values_are_booked_and_absent_without_remat(gradient_programs):
    kept = [v for kind in TINY["pattern"] for v in KEPT_BY_KIND[kind]]
    assert gradient_programs[True][1] == {
        "remat.layers": 9, "remat.kept_values": len(kept),
        # layer 0's [z | xBC | dt] is float32 under exact_first_layer
        "remat.kept_bytes": _bytes(kept) + 2 * 40 * 1028 * 2}
    assert gradient_programs[False][1] == {}


@pytest.mark.parametrize("kind", KEPT_BY_KIND, ids=["mamba", "experts", "attention"])
def test_a_checkpointed_layer_keeps_the_listed_values_and_nothing_else(kind):
    """What one layer under the model's policy hands its backward, by JAX's
    own account: its arguments and exactly the listed values, so no mixer's
    last product (``out_proj``'s, ``down``'s: the next layer keeps the sum as
    its own input) and nothing the elementwise rest makes."""
    from jax._src.ad_checkpoint import saved_residuals
    telemetry.registry().clear()
    cfg = nemotron_h.NemotronHConfig(attention_impl="flash", ssm_impl="pallas",
                                     rows_bound=40, **TINY)
    block = nemotron_h.NemotronHBlock(cfg, kind)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, cfg.d_model))
    params = block.init(jax.random.PRNGKey(1), x)["params"]

    @functools.partial(jax.checkpoint, policy=common.keeping(nemotron_h.KEPT))
    def layer(params, x):
        y, term = block.apply({"params": params}, x)
        return jnp.sum(jnp.square(y)) + term

    made = [(aval.shape, str(aval.dtype))
            for aval, how in saved_residuals(layer, params, x)
            if "from the argument" not in how]
    assert sorted(made) == sorted(KEPT_BY_KIND[kind])
    assert telemetry.gauge("remat.kept_values").value == len(made)
    assert telemetry.gauge("remat.kept_bytes").value == _bytes(made)


def test_the_three_families_share_the_mixtures_code_and_none_copies_it():
    for name in ("RoutedShare", "check_share", "balance_expert_bias",
                 "expert_loads", "sown_loads"):
        assert getattr(nemotron_h, name) is getattr(afmoe, name) \
            is getattr(lfm2_moe, name) is getattr(moe, name)
    assert nemotron_h.make_optimizer is afmoe.make_optimizer \
        is moe.balanced_optimizer
    assert nemotron_h.PlainMLP is moe.PlainMLP
    with open(nemotron_h.__file__) as f:
        source = f.read()
    assert "def balance(" not in source and "routed_experts(" not in source
    assert "gmm(" not in source and "pallas_call" not in source
    # the expert's form is an argument of the shared code, traced under its gauge
    cfg = nemotron_h.NemotronHConfig(dtype=jnp.float32, **TINY)
    layer = _share(cfg)
    h = jnp.zeros((1, 8, 64))
    params = layer.init(jax.random.PRNGKey(0), h)["params"]
    layer.apply({"params": params}, h)
    assert telemetry.gauge("moe.expert_form").value == 2
    with pytest.raises(ValueError, match="expert form"):
        moe.routed_experts(h[0], jnp.zeros((8, 8)), params["up"], params["up"],
                           params["down"], top_k=2, form="relu2")


def test_an_unknown_impl_or_layer_kind_is_refused():
    with pytest.raises(ValueError, match="Unknown ssm_impl"):
        nemotron_h.NemotronHConfig(ssm_impl="mosaic")
    with pytest.raises(ValueError, match="Unknown attention_impl"):
        nemotron_h.NemotronHConfig(attention_impl="ring")
    with pytest.raises(ValueError, match="pattern must be of"):
        nemotron_h.NemotronHConfig(pattern="MEA")
    with pytest.raises(ValueError, match="inside the router's width"):
        nemotron_h.NemotronHConfig(experts_held=8, first_expert_held=124)
    with pytest.raises(ValueError, match="over n_groups"):
        nemotron_h.NemotronHConfig(mamba_heads=6, n_groups=4)
    with pytest.raises(ValueError, match="exact_first_layer"):
        nemotron_h.NemotronHConfig(pattern="EM", exact_first_layer=True)


def test_a_step_through_the_normal_path_moves_the_bias_by_the_rule():
    """``AutoDist(...)`` session and ``train()``, nothing on the side, with
    the scan's kernels, flash, the fused head and every layer recomputed, in
    bfloat16: after one optimizer step every expert-bias leaf has moved by
    ``coeff * (sign(mean c - c_e) - its mean)``; the other leaves moved by
    AdamW, the scan's own among them; three steps and the loss falls."""
    cfg = nemotron_h.NemotronHConfig(
        dtype=jnp.bfloat16, attention_impl="flash", ssm_impl="pallas",
        fused_head=True, remat=True, load_balance_coeff=1e-3,
        **dict(TINY, pattern="ME*M"))
    model, params = nemotron_h.init_params(cfg)
    params = _stirred(params, scale=0.05)
    batch = nemotron_h.synthetic_batch(cfg, batch_size=8, seq_len=32)
    loss_fn = nemotron_h.make_loss_fn(model)
    optimizer = nemotron_h.make_optimizer(1e-2, cfg.load_balance_coeff)
    grads = jax.grad(loss_fn)(params, {"tokens": jnp.asarray(batch["tokens"])})

    def one_run(steps):
        ad = AutoDist(strategy_builder=AllReduce())
        runner = ad.create_distributed_session(loss_fn, params, optimizer,
                                               example_batch=batch)
        losses = []
        final = train(runner, params, iter([batch] * steps), steps=steps,
                      log_every=1,
                      on_metrics=lambda step, loss, rate: losses.append(float(loss)))
        return jax.device_get(final.params), losses

    after, _ = one_run(1)
    load_error = np.asarray(grads["block_1"]["moe"]["expert_bias"])
    assert np.abs(load_error).max() > 0
    signs = np.sign(load_error)        # sign(c_e - mean c)
    want = -cfg.load_balance_coeff * (signs - signs.mean())
    moved = np.asarray(after["block_1"]["moe"]["expert_bias"]) \
        - np.asarray(params["block_1"]["moe"]["expert_bias"])
    np.testing.assert_allclose(moved, want, atol=1e-7)
    for leaf in (("block_0", "mamba", "A_log"), ("block_0", "mamba", "dt_bias"),
                 ("block_0", "mamba", "conv"), ("block_3", "mamba", "in_proj", "kernel"),
                 ("block_1", "moe", "up"), ("block_1", "moe", "shared", "down", "kernel"),
                 ("block_2", "attn", "key", "kernel"), ("lm_head", "kernel")):
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
    plain = nemotron_h.NemotronHConfig(**TINY)
    kernels = dataclasses.replace(plain, ssm_impl="pallas", attention_impl="flash",
                                  fused_head=True, remat=True)
    a = nemotron_h.init_params(plain, jax.random.PRNGKey(3))[1]
    b = nemotron_h.init_params(kernels, jax.random.PRNGKey(3))[1]
    jax.tree_util.tree_map(np.testing.assert_array_equal, a, b)
    assert math.isfinite(float(jnp.abs(a["embed"]["embedding"]).max()))
