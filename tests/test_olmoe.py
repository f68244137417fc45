"""OLMoE (``models/olmoe.py``): the system's loss and gradients against the
plain reference the benchmark checks it with on the chip
(``benchmark/reference/olmoe.py``), the dropless routing, RoPE and QK-norm
against closed forms, and three steps through the normal path. Tiny widths on
the CPU mesh; kernels in interpret mode."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import AutoDist, train
from autodist_tpu.models import common, moe, olmoe
from autodist_tpu.strategy import AllReduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tests import reference_programs  # noqa: E402

TINY = dict(vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_expert=32,
            n_experts=8, top_k=2, max_len=32)


def _rel_l2(a, b):
    leaves = lambda t: jax.tree_util.tree_leaves(t)  # noqa: E731
    num = sum(float(jnp.sum(jnp.square(x - y))) for x, y in zip(leaves(a), leaves(b)))
    return (num / sum(float(jnp.sum(jnp.square(y))) for y in leaves(b))) ** 0.5


# float32 activations: the two programs compute the same numbers in another
# order (sorted rows and grouped products against every expert under a mask),
# so they agree to float32 rounding. bfloat16 activations: each of some dozen
# products a token passes rounds to 2^-8, which adds up to parts in a thousand
# in the loss and about a percent in the whole gradient (0.8% here); a dropped
# term — a residual, the z-loss, the unnormalised weights — moves either by
# far more, and float32 activations against this bound would pass at 1e-5.
@pytest.mark.parametrize("dtype,attention,fused,loss_tol,grad_tol", [
    (jnp.float32, "dot", False, 1e-5, 1e-5),
    (jnp.float32, "flash", True, 1e-5, 1e-5),
    (jnp.bfloat16, "flash", True, 1e-3, 3e-2),
], ids=["f32-xla", "f32-kernels", "bf16-kernels"])
def test_loss_and_gradients_match_the_plain_reference(dtype, attention, fused,
                                                      loss_tol, grad_tol):
    cfg = olmoe.OlmoeConfig(dtype=dtype, attention_impl=attention,
                            fused_head=fused, **TINY)
    model, params = olmoe.init_params(cfg, jax.random.PRNGKey(1))
    batch = {"tokens": jnp.asarray(
        olmoe.synthetic_batch(cfg, 4, 32, seed=3)["tokens"])}
    loss, grads = jax.jit(jax.value_and_grad(olmoe.make_loss_fn(model)))(
        params, batch)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = reference_programs.value_and_grad(
            "olmoe", n_heads=cfg.n_heads, n_layers=cfg.n_layers,
            top_k=cfg.top_k, rms_eps=cfg.rms_eps, rope_theta=cfg.rope_theta,
            load_balance_weight=cfg.load_balance_weight,
            router_z_weight=cfg.router_z_weight)(params, batch)
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) <= loss_tol
    assert _rel_l2(grads, ref_grads) <= grad_tol
    assert {str(g.dtype) for g in jax.tree_util.tree_leaves(grads)} == {"float32"}


# PR 29 put a shared sort under ``topk_route`` and a second, compacting path
# beside ``routed_experts`` (one chip's share of the experts, for
# ``models/afmoe.py``), and a window and grouped KV heads into the flash
# kernels. OLMoE holds every expert, attends without a window and has as many
# KV heads as query heads: its program is what it was, and its loss at the
# tiny size is the parent's (commit e714195) bit for bit, on this backend.
@pytest.mark.parametrize("dtype,attention,fused,parent_loss", [
    (jnp.float32, "dot", False, "0x1.6651600000000p+2"),
    (jnp.float32, "flash", True, "0x1.66515e0000000p+2"),
    (jnp.bfloat16, "flash", True, "0x1.6651900000000p+2"),
], ids=["f32-xla", "f32-kernels", "bf16-kernels"])
def test_the_loss_is_bit_for_bit_the_parents(dtype, attention, fused, parent_loss):
    cfg = olmoe.OlmoeConfig(dtype=dtype, attention_impl=attention,
                            fused_head=fused, **TINY)
    model, params = olmoe.init_params(cfg, jax.random.PRNGKey(1))
    batch = {"tokens": jnp.asarray(
        olmoe.synthetic_batch(cfg, 4, 32, seed=3)["tokens"])}
    loss = jax.jit(olmoe.make_loss_fn(model))(params, batch)
    assert float(loss).hex() == parent_loss


# PR 42 put one decoder shell (``models/decoder.py``: the stack, the loss, the
# init) under the five dropless families and one share module under the four
# sigmoid-routed ones (``models/moe.py`` ``RoutedShare``). Their programs are
# what they were: each family's loss at its own test file's tiny size is the
# parent's (commit 8dd3c63) bit for bit, on this backend, plain in float32 and
# in bfloat16 with every kernel and option its cell runs.
_KERNELS = dict(dtype=jnp.bfloat16, attention_impl="flash", fused_head=True)
_SHELLED = {
    "afmoe": ("AfmoeConfig", {}, "0x1.644fe60000000p+2", "0x1.644b2a0000000p+2"),
    "lfm2_moe": ("Lfm2MoeConfig", dict(conv_impl="pallas"),
                 "0x1.675ee60000000p+2", "0x1.675f0c0000000p+2"),
    "nemotron_h": ("NemotronHConfig",
                   dict(ssm_impl="pallas", remat=True, exact_first_layer=True),
                   "0x1.63c8680000000p+2", "0x1.63c91c0000000p+2"),
    "deepseek_v3": ("DeepseekV3Config", dict(remat=True),
                    "0x1.6514920000000p+2", "0x1.6513f40000000p+2"),
}


@pytest.mark.parametrize("setting", ["f32-xla", "bf16-kernels"])
@pytest.mark.parametrize("family", list(_SHELLED))
def test_the_shelled_families_losses_are_bit_for_bit_the_parents(family, setting):
    import importlib
    module = importlib.import_module(f"autodist_tpu.models.{family}")
    tiny = importlib.import_module(f"test_{family}").TINY
    config, cells_own, plain_loss, kernels_loss = _SHELLED[family]
    options, parent_loss = {
        "f32-xla": (dict(dtype=jnp.float32), plain_loss),
        "bf16-kernels": (dict(_KERNELS, **cells_own), kernels_loss)}[setting]
    cfg = getattr(module, config)(**dict(tiny, **options))
    model, params = module.init_params(cfg, jax.random.PRNGKey(1))
    batch = {"tokens": jnp.asarray(
        module.synthetic_batch(cfg, 4, 32, seed=3)["tokens"])}
    loss = jax.jit(module.make_loss_fn(model))(params, batch)
    assert float(loss).hex() == parent_loss


def test_topk_route_is_dropless():
    tokens, experts, k = 48, 8, 3
    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(0),
                                             (tokens, experts)) * 3.0)
    route = moe.topk_route(probs, k)
    # every token x slot lands: nothing dropped, nothing padded
    assert int(route.group_sizes.sum()) == tokens * k
    np.testing.assert_array_equal(
        route.group_sizes, np.bincount(np.asarray(route.indices).ravel(),
                                       minlength=experts))
    # the weights are the softmax values as they are, largest first
    want = np.sort(np.asarray(probs), axis=-1)[:, ::-1][:, :k]
    np.testing.assert_allclose(route.weights, want, rtol=1e-6)
    assert float(route.weights.sum(axis=-1).max()) < 1.0
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(probs), np.asarray(route.indices), 1),
        route.weights, rtol=1e-6)
    # the permutation sorts the rows by expert, stably, and its inverse restores them
    flat = np.asarray(route.indices).ravel()
    np.testing.assert_array_equal(route.perm, np.argsort(flat, kind="stable"))
    np.testing.assert_array_equal(np.asarray(route.perm)[route.inv_perm],
                                  np.arange(tokens * k))
    np.testing.assert_array_equal(flat[route.perm][route.inv_perm], flat)


def test_routed_experts_equal_every_expert_under_a_mask_and_build_no_capacity_tensor():
    tokens, d, w, experts, k = 40, 16, 24, 8, 2
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(keys[0], (tokens, d))
    probs = jax.nn.softmax(jax.random.normal(keys[1], (tokens, experts)) * 2.0)
    gate, up = (jax.random.normal(key, (experts, d, w)) * 0.3 for key in keys[2:4])
    down = jax.random.normal(keys[4], (experts, w, d)) * 0.3

    def dense(x, probs, gate, up, down):
        kth = jnp.sort(probs, axis=-1)[:, experts - k]
        gates = jnp.where(probs >= kth[:, None], probs, 0.0)
        every = jnp.einsum("tew,ewd->ted", jax.nn.silu(
            jnp.einsum("td,edw->tew", x, gate)) * jnp.einsum("td,edw->tew", x, up),
            down)
        return jnp.einsum("te,ted->td", gates, every)

    routed = lambda *a: moe.routed_experts(*a, top_k=k)[0]  # noqa: E731
    np.testing.assert_allclose(routed(x, probs, gate, up, down),
                               dense(x, probs, gate, up, down),
                               rtol=1e-5, atol=1e-5)
    args = (x, probs, gate, up, down)
    got = jax.jit(jax.grad(lambda *a: routed(*a).sum(), argnums=(0, 1, 2, 3, 4)))(*args)
    want = jax.jit(jax.grad(lambda *a: dense(*a).sum(), argnums=(0, 1, 2, 3, 4)))(*args)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5)
    # Nothing in the routed program, forward or backward, is as large as a
    # [tokens, experts, anything] tensor: the largest value is a bank or
    # the [tokens x k, width] rows.
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: routed(*a).sum(),
                                    argnums=(0, 2, 3, 4)))(*args)
    largest = max(int(np.prod(v.aval.shape)) for eqn in jaxpr.eqns
                  for v in eqn.outvars)
    assert largest <= max(experts * d * w, tokens * k * w)
    assert largest < tokens * experts * min(d, w)


def test_rope_is_a_rotation_by_position_times_frequency():
    length, heads, d, theta = 9, 2, 8, 100.0
    x = jax.random.normal(jax.random.PRNGKey(0), (1, length, heads, d))
    y = common.rope(x, jnp.arange(length), theta)
    np.testing.assert_allclose(y[:, 0], x[:, 0], atol=1e-6)   # position 0: identity
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),    # a rotation keeps length
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    for i in range(d // 2):
        angle = np.arange(length) * theta ** (-2.0 * i / d)
        a, b = np.asarray(x[0, :, 0, i]), np.asarray(x[0, :, 0, i + d // 2])
        np.testing.assert_allclose(y[0, :, 0, i],
                                   a * np.cos(angle) - b * np.sin(angle), atol=1e-5)
        np.testing.assert_allclose(y[0, :, 0, i + d // 2],
                                   b * np.cos(angle) + a * np.sin(angle), atol=1e-5)
    # scores depend on the distance between positions only
    q = jnp.broadcast_to(x[:, :1], x.shape)
    r = common.rope(q, jnp.arange(length), theta)
    scores = jnp.einsum("bqhd,bkhd->bhqk", r, r)[0, 0]
    np.testing.assert_allclose(scores[2, 5], scores[4, 7], rtol=1e-4)


def test_rms_norm_and_qk_norm_over_the_whole_projection():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 64)) * 4.0
    norm = common.RMSNorm(eps=1e-5)
    params = {"params": {"scale": jnp.full((64,), 0.5)}}
    y = norm.apply(params, x)
    np.testing.assert_allclose(
        y, 0.5 * x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-5),
        rtol=1e-5)
    np.testing.assert_allclose(jnp.mean(jnp.square(y), -1), 0.25, rtol=1e-3)
    assert norm.apply(params, x.astype(jnp.bfloat16)).dtype == jnp.float32
    # QK-norm: q is normalised over all 64 features of the projection, not a
    # head at a time, so the 4 heads of 16 keep their relative sizes.
    cfg = olmoe.OlmoeConfig(dtype=jnp.float32, **TINY)
    model, p = olmoe.init_params(cfg)
    tokens = jnp.arange(16).reshape(2, 8)
    _, state = model.apply({"params": p}, tokens, capture_intermediates=(
        lambda module, _: module.name == "q_norm"))
    q = state["intermediates"]["block_0"]["attn"]["q_norm"]["__call__"][0]
    np.testing.assert_allclose(jnp.mean(jnp.square(q), -1), 1.0, rtol=1e-3)
    per_head = jnp.mean(jnp.square(q.reshape(2, 8, 4, 16)), -1)
    assert float(jnp.abs(per_head - 1.0).max()) > 1e-2


def test_three_steps_through_the_normal_path_and_the_loss_falls():
    cfg = olmoe.OlmoeConfig(dtype=jnp.bfloat16, attention_impl="flash",
                            fused_head=True, **TINY)
    model, params = olmoe.init_params(cfg)
    batch = olmoe.synthetic_batch(cfg, batch_size=8, seq_len=32)
    ad = AutoDist(strategy_builder=AllReduce())
    runner = ad.create_distributed_session(
        olmoe.make_loss_fn(model), params, optax.adamw(1e-2), example_batch=batch)
    losses = []
    train(runner, params, iter([batch] * 3), steps=3, log_every=1,
          on_metrics=lambda step, loss, rate: losses.append(float(loss)))
    # the meter's first report is of step 2: the first step warms the loop up
    assert len(losses) >= 2 and all(np.isfinite(losses))
    assert losses[-1] < losses[0]
