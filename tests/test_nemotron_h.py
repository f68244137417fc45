"""Nemotron-H (``models/nemotron_h.py``): the system's loss and whole gradient
against the plain reference the benchmark checks it with on the chip
(``benchmark/reference/nemotron_h.py``) for the cell's own pattern
``MEMEM*EME``, the parameter tree the equations name, the gated grouped norm
and the initialisation by hand, one chip's share of the ``relu2`` experts (the
16 shares of a 128-wide router and the shared expert once add up to the uncut
layer), the code the three sigmoid-routed families share (``models/moe.py``)
and a step through the normal path; per-layer recomputation is
``test_nemotron_h_recompute.py``. Tiny widths (``tests/nemotron_tiny.py``) on
the CPU mesh; kernels in interpret mode."""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import AutoDist, telemetry, train
from autodist_tpu.models import afmoe, lfm2_moe, moe, nemotron_h
from autodist_tpu.strategy import AllReduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tests import reference_programs  # noqa: E402
from tests.nemotron_tiny import TINY, stirred  # noqa: E402


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
    cfg = nemotron_h.NemotronHConfig(dtype=dtype, attention_impl=attention,
                                     ssm_impl=ssm, fused_head=fused, remat=remat,
                                     exact_first_layer=exact, rows_bound=40,
                                     **TINY)
    model, params = nemotron_h.init_params(cfg, jax.random.PRNGKey(1))
    params = stirred(params)
    batch = {"tokens": jnp.asarray(
        nemotron_h.synthetic_batch(cfg, 2, 40, seed=3)["tokens"])}
    loss, grads = jax.jit(jax.value_and_grad(nemotron_h.make_loss_fn(model)))(
        params, batch)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = reference_programs.value_and_grad(
            "nemotron_h", **_reference_kwargs(cfg))(params, batch)
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


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("y_dtype,z_dtype,out_dtype,length", [
    (jnp.float32, jnp.float32, jnp.float32, 40),
    (jnp.bfloat16, jnp.bfloat16, jnp.bfloat16, 64),
    # the exact first layer's: the scan's bfloat16 y beside a float32 z, a
    # float32 result; and a length past one block of rows, ragged
    (jnp.bfloat16, jnp.float32, jnp.float32, 1100),
], ids=["float32", "bfloat16", "exact-layer-ragged"])
def test_the_gated_norm_operator_is_gated_group_norms_equations(
        impl, y_dtype, z_dtype, out_dtype, length):
    """``ops/gated_norm.py`` handed ``z`` as the first columns of a wider
    array (``[z | xBC | dt]`` as ``in_proj`` wrote it) against the equations
    on the cut-out ``z``, float32 arithmetic either way: the value, ``dy``,
    ``dz`` (zero behind ``z``'s columns) and the scale's gradient, in the
    operands' dtypes."""
    from autodist_tpu.ops.gated_norm import gated_norm
    d, groups, behind = 256, 2, 128 + 64
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    y = jax.random.normal(keys[0], (2, length, d), y_dtype)
    wide = jax.random.normal(keys[1], (2, length, d + behind), z_dtype)
    scale = 1.0 + 0.1 * jax.random.normal(keys[2], (d,))
    weight = jax.random.normal(keys[3], (2, length, d))

    def value_and_grads(fn):
        def loss(y, wide, scale):
            out = fn(y, wide, scale)
            return jnp.sum(out.astype(jnp.float32) * weight), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(y, wide, scale)
        return out, grads

    got, got_grads = value_and_grads(lambda y, wide, scale: gated_norm(
        y, wide, scale, groups, 1e-5, out_dtype, impl))
    want, want_grads = value_and_grads(
        lambda y, wide, scale: nemotron_h.gated_group_norm(
            y, wide[..., :d], scale, groups, 1e-5).astype(out_dtype))
    assert got.dtype == out_dtype and got.shape == y.shape
    # float32: sums in another order; bfloat16: one rounding of them
    tolerance = 1e-5 if out_dtype == jnp.float32 else 2 ** -7
    np.testing.assert_allclose(got.astype(jnp.float32), want.astype(jnp.float32),
                               rtol=tolerance, atol=tolerance)
    for name, g, r in zip(("dy", "dz", "dscale"), got_grads, want_grads):
        assert g.shape == r.shape and g.dtype == r.dtype, name
        g, r = g.astype(jnp.float32), r.astype(jnp.float32)
        loose = 1e-4 if y_dtype == jnp.float32 else 2e-2   # a bfloat16 dy
        assert float(jnp.linalg.norm(g - r)) <= loose * float(jnp.linalg.norm(r)), name
    assert float(jnp.abs(got_grads[1][..., d:]).max()) == 0.0


def test_the_gated_norm_operator_refuses_what_it_cannot_take():
    from autodist_tpu.ops.gated_norm import gated_norm
    y, scale = jnp.zeros((1, 16, 192)), jnp.ones((192,))
    with pytest.raises(ValueError, match="Unknown gated norm impl"):
        gated_norm(y, y, scale, 2, 1e-5, impl="mosaic")
    with pytest.raises(ValueError, match=r"want \[B, L, d\]"):
        gated_norm(y, y[..., :128], scale, 2, 1e-5)
    with pytest.raises(ValueError, match="groups dividing d"):
        gated_norm(y, y, scale, 5, 1e-5)
    with pytest.raises(ValueError, match="a run of 96 columns"):
        gated_norm(y, y, scale, 2, 1e-5, impl="pallas")
    assert gated_norm(y, y, scale, 2, 1e-5).shape == y.shape


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
    whole = stirred(whole)
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
    params = stirred(params, scale=0.05)
    batch = nemotron_h.synthetic_batch(cfg, batch_size=8, seq_len=32)
    loss_fn = nemotron_h.make_loss_fn(model)
    optimizer = nemotron_h.make_optimizer(1e-2, cfg.load_balance_coeff)
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
