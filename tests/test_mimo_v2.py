"""MiMo-V2 (``models/mimo_v2.py``) at a tiny size on the CPU, the kernels in
interpret mode: loss and every gradient against the plain float32 reference
(``benchmark/reference/mimo_v2.py``), the four shares of a 32-expert layer
against the uncut reference, a sink or a router kept in bfloat16 against a
stated tolerance, partial rotary against the whole-head form, and the
expert-bias rule under ``strategy.FullySharded`` on a four-device mesh, where
the expert banks are stored as quarters and the load error is the global
batch's."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.models import mimo_v2
from autodist_tpu.models.common import rope
from autodist_tpu.strategy import FullySharded

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tests import reference_programs  # noqa: E402

# A full dense layer and a sliding expert layer (each kind of attention and
# of feed-forward once: two layers' compile time a case); 8 query heads over 2
# (full) or 4 (sliding) KV heads, keys 48 wide (16 of them turned) over values
# 32, a window of 16; the share: experts 2-4 of 8, top-3.
TINY = dict(vocab_size=256, d_model=64, n_heads=8, n_kv_heads=2,
            swa_n_kv_heads=4, head_dim=48, v_head_dim=32,
            layer_pattern=(0, 1), moe_layer_freq=(0, 1), d_ff=96,
            d_expert=24, n_experts_routed=8, experts_held=3,
            first_expert_held=2, top_k=3, window=16, max_len=64)


def _rel_l2(a, b):
    leaves = lambda t: jax.tree_util.tree_leaves(t)  # noqa: E731
    num = sum(float(jnp.sum(jnp.square(x - y))) for x, y in zip(leaves(a), leaves(b)))
    return (num / sum(float(jnp.sum(jnp.square(y))) for y in leaves(b))) ** 0.5


def _reference_kwargs(cfg):
    return dict(layer_pattern=cfg.layer_pattern,
                dense=tuple(not moe for moe in cfg.moe_layer_freq),
                n_heads=cfg.n_heads, head_dim=cfg.head_dim,
                v_head_dim=cfg.v_head_dim, window=cfg.window,
                rotary_dim=cfg.rotary_dim, rope_theta=cfg.rope_theta,
                swa_rope_theta=cfg.swa_rope_theta, value_scale=cfg.value_scale,
                top_k=cfg.top_k, rms_eps=cfg.rms_eps,
                first_expert_held=cfg.first_expert_held)


def _stirred(params, scale=0.2):
    """The leaves that init sets to constants (zeros, ones), drawn: sinks on
    both sides of the scores, an ``expert_bias`` large enough to change
    choices, norm weights that a dropped factor would show in."""
    def draw(path, x):
        if path[-1].key not in ("expert_bias", "scale", "sink"):
            return x
        key = jax.random.PRNGKey(sum(map(ord, jax.tree_util.keystr(path))))
        return x + (5 * scale if path[-1].key == "sink" else scale) \
            * jax.random.normal(key, x.shape)
    return jax.tree_util.tree_map_with_path(draw, params)


def _batch(cfg, sequences=2, length=40, seed=3):
    return {"tokens": jnp.asarray(
        mimo_v2.synthetic_batch(cfg, sequences, length, seed=seed)["tokens"])}


@functools.lru_cache(maxsize=None)
def _system(dtype, kernels: bool):
    """``(config, model, jitted value_and_grad of its loss)``, one compile a
    (dtype, path) for every case that runs the tiny stack."""
    cfg = mimo_v2.MimoV2Config(
        dtype=dtype, attention_impl="flash" if kernels else "dot",
        fused_head=kernels, remat=kernels, rows_bound=40, **TINY)
    model = mimo_v2.MimoV2(cfg)
    return cfg, model, jax.jit(jax.value_and_grad(mimo_v2.make_loss_fn(model)))


def _reference(cfg, params, batch):
    with jax.default_matmul_precision("highest"):
        return reference_programs.value_and_grad(
            "mimo_v2", **_reference_kwargs(cfg))(params, batch)


# float32 activations agree with the reference to rounding (summation order
# and the online softmax; 2e-5 of the gradient's norm), whichever path
# computes them; bfloat16 sublayers (2^-8 a rounding) to parts in a thousand
# of the loss and a few percent of the gradient, as the other share families
# read at this size. A dropped term, scale, rotation or sink moves either by
# tens of percent.
@pytest.mark.parametrize("dtype,kernels,loss_tol,grad_tol", [
    (jnp.float32, False, 1e-5, 2e-5),
    (jnp.float32, True, 1e-5, 2e-5),
    (jnp.bfloat16, True, 2e-3, 4e-2),
], ids=["float32-xla", "float32-kernels", "bfloat16-kernels"])
def test_loss_and_gradients_match_the_plain_reference(dtype, kernels, loss_tol,
                                                      grad_tol):
    cfg, _, program = _system(dtype, kernels)
    params = _stirred(mimo_v2.init_params(cfg, jax.random.PRNGKey(1))[1])
    batch = _batch(cfg)
    loss, grads = program(params, batch)
    ref_loss, ref_grads = _reference(cfg, params, batch)
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) <= loss_tol
    assert _rel_l2(grads, ref_grads) <= grad_tol
    assert {str(g.dtype) for g in jax.tree_util.tree_leaves(grads)} == {"float32"}
    # every leaf takes a gradient: the sinks and both kinds' projections
    for block, leaves in (("block_0", ("query", "key", "value", "out")),
                          ("block_1", ("query", "key", "value", "out"))):
        for leaf in leaves:
            assert float(jnp.abs(grads[block]["attn"][leaf]["kernel"]).max()) > 0
    sinks = grads["block_1"]["attn"]["sink"]
    assert sinks.shape == (8,) and float(jnp.abs(sinks).min()) > 0
    # the sinks' own gradient within the whole gradient's tolerance
    want = ref_grads["block_1"]["attn"]["sink"]
    assert float(jnp.linalg.norm(sinks - want) / jnp.linalg.norm(want)) <= grad_tol
    assert "sink" not in params["block_0"]["attn"]       # full layers have none
    d_bias = grads["block_1"]["moe"]["expert_bias"]
    assert abs(float(d_bias.sum())) < 1e-6 and float(jnp.abs(d_bias).max()) > 0
    if kernels:
        assert telemetry.gauge("attn.sink_layers").value == 1
        visible = telemetry.gauge("attn.band_pairs_visible").value
        computed = telemetry.gauge("attn.band_pairs_computed").value
        # 2 sequences x 8 heads x one sliding layer; 40 queries see 16 keys
        # at most; one tile of 40 x 40 is computed
        assert visible == 2 * 8 * (16 * 17 // 2 + 24 * 16)
        assert computed == 2 * 8 * 40 * 40


def test_the_tiny_stack_has_the_parameters_the_equations_name():
    cfg = mimo_v2.MimoV2Config(dtype=jnp.float32, **TINY)
    _, params = mimo_v2.init_params(cfg)
    count = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))  # noqa: E731
    full = 64 * 8 * 48 + 64 * 2 * 48 + 64 * 2 * 32 + 8 * 32 * 64
    sliding = 64 * 8 * 48 + 64 * 4 * 48 + 64 * 4 * 32 + 8 * 32 * 64 + 8
    experts = 3 * 3 * 64 * 24 + 64 * 8 + 8
    assert count(params["block_0"]) == full + 2 * 64 + 3 * 64 * 96
    assert count(params["block_1"]) == sliding + 2 * 64 + experts
    assert count(params) == count(params["block_0"]) + count(params["block_1"]) \
        + 2 * 256 * 64 + 64
    assert cfg.rotary_dim == 16 and mimo_v2.MimoV2Config().rotary_dim == 64
    assert mimo_v2.MimoV2Config().layer_pattern[6:12] == (1, 1, 1, 1, 1, 0)


def test_partial_rope_turns_the_leading_columns_and_passes_the_rest():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, 48))
    positions = jnp.arange(12)
    turned = rope(x, positions, 1e4, 16)
    np.testing.assert_array_equal(turned[..., 16:], x[..., 16:])
    np.testing.assert_allclose(turned[..., :16], rope(x[..., :16], positions, 1e4),
                               atol=1e-6)
    # the whole head where rotary_dim is the head, as before there was one
    np.testing.assert_array_equal(rope(x, positions, 1e4, 48),
                                  rope(x, positions, 1e4))
    # a score depends on the distance alone
    q, k = turned[:, 7], rope(x[..., ::-1], positions, 1e4, 16)[:, 5]
    q2, k2 = (rope(t, positions + 3, 1e4, 16) for t in (x, x[..., ::-1]))
    np.testing.assert_allclose(jnp.sum(q * k, -1), jnp.sum(q2[:, 7] * k2[:, 5], -1),
                               rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError, match="even"):
        rope(x, positions, 1e4, 15)


def test_the_four_shares_of_eight_with_attention_once_add_up_to_the_uncut_layer():
    """What the guide asks of a share: the routed parts that the shares
    ``first_expert_held`` = 0, 8, 16, 24 of a 32-wide router give, with
    attention and the residual stream (which every rank computes alike)
    counted once, add up to what the uncut reference gives for the whole
    layer, a sliding layer with its sinks. The system's block on each share's
    slice of one parameter tree; the reference on the whole tree. No shared
    expert: a share whose banks are zero adds nothing."""
    from benchmark.reference import mimo_v2 as reference
    wide = dict(TINY, d_model=32, d_expert=16, n_experts_routed=32, top_k=6)
    cfg = mimo_v2.MimoV2Config(dtype=jnp.float32, **dict(
        wide, experts_held=32, first_expert_held=0))
    tokens = 40
    x = jax.random.normal(jax.random.PRNGKey(3), (1, tokens, cfg.d_model))
    whole = _stirred(mimo_v2.MimoV2Block(cfg, True, False).init(
        jax.random.PRNGKey(2), x[:, :4])["params"])
    banks = ("gate", "up", "down")

    def share(first, held, down=None):
        share_cfg = mimo_v2.MimoV2Config(dtype=jnp.float32, **dict(
            wide, experts_held=held, first_expert_held=first, rows_bound=24))
        mine = {name: whole["moe"][name][first:first + held] for name in banks}
        if down is not None:
            mine["down"] = down
        params = dict(whole, moe=dict(whole["moe"], **mine))
        (out, _), sown = mimo_v2.MimoV2Block(share_cfg, True, False).apply(
            {"params": params}, x, mutable=["intermediates"])
        return out, sown["intermediates"]["moe"]["load"][0]

    @jax.jit    # one program: interpreted kernels run eagerly operation by operation
    def everything(whole, x):
        # what every rank computes alike: the stream and attention
        alike, _ = share(0, 8, down=jnp.zeros_like(whole["moe"]["down"][:8]))
        return alike, [share(first, 8) for first in range(0, 32, 8)], share(0, 32)

    alike, shares, (one, _) = everything(whole, x)
    total, loads = alike, []
    for out, load in shares:
        total = total + (out - alike)
        loads.append(load)
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference.block(
            whole, x, sliding=True, dense=False, eps=cfg.rms_eps,
            attn=dict(n_heads=cfg.n_heads, head_dim=cfg.head_dim,
                      v_head_dim=cfg.v_head_dim, window=cfg.window,
                      rotary_dim=cfg.rotary_dim, value_scale=cfg.value_scale,
                      theta=cfg.swa_rope_theta),
            route=dict(top_k=cfg.top_k, route_norm=True, route_scale=1.0,
                       first_expert_held=0))
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=1e-5)
    # every rank makes the same choice over the whole width
    for load in loads:
        np.testing.assert_array_equal(load, loads[0])
    assert float(loads[0].sum()) == tokens * 6
    # and the whole bank in one layer is the same uncut result
    np.testing.assert_allclose(one, uncut, rtol=1e-4, atol=1e-5)


def _narrowed(params, leaf: str):
    """``params`` with every leaf named ``leaf`` rounded to bfloat16 and back:
    what storing it in bfloat16 would feed the float32 arithmetic."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x.astype(jnp.bfloat16).astype(jnp.float32)
        if path[-1].key == leaf else x, params)


@pytest.mark.parametrize("leaf", ["sink", "router"])
def test_a_bfloat16_stored_sink_or_router_fails_the_float32_tolerance(leaf):
    """The float32 system agrees with the reference to 2e-5 of the gradient
    (the first test, this very program). With the sinks or the router's matrix
    rounded to bfloat16's 8 bits, as storage in that precision would leave
    them, that tolerance is exceeded several times over: the sinks move every
    sliding row's denominator; a router's rounding moves every score and
    flips choices near the k-th place. The router's matrix is first scaled
    to a trained one's logits (init's normal(0.02) gives logits of a
    hundredth, whose rounding moves nothing that shows), and the exact system
    is held to the tolerance on those parameters too."""
    cfg, _, program = _system(jnp.float32, True)
    params = _stirred(mimo_v2.init_params(cfg, jax.random.PRNGKey(1))[1])
    if leaf == "router":
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: 50.0 * x if path[-1].key == "router" else x, params)
    batch = _batch(cfg)
    _, ref_grads = _reference(cfg, params, batch)
    assert _rel_l2(program(params, batch)[1], ref_grads) <= 2e-5
    # read: some 2e-4 with the sinks rounded, 3.5e-5 with the router's matrix
    assert _rel_l2(program(_narrowed(params, leaf), batch)[1], ref_grads) > 1.5 * 2e-5


def test_an_unknown_impl_pattern_or_share_is_refused():
    for bad in (dict(attention_impl="paged"), dict(layer_pattern=(0, 2)),
                dict(moe_layer_freq=(0, 1, 1)), dict(swa_n_kv_heads=3),
                dict(partial_rotary_factor=0.33), dict(experts_held=7),
                dict(top_k=9)):
        with pytest.raises(ValueError):
            mimo_v2.MimoV2Config(**dict(TINY, **bad))


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual CPU devices")
def test_the_bias_rule_under_fully_sharded_moves_the_bias_by_the_global_load_error():
    """``strategy.FullySharded`` on four devices, a sequence a device (a dense
    and a sliding expert layer, dot attention and the plain head around the
    share's kernels; the sharded step with flash and its sinks is lowered in
    ``tests/test_device_names.py``): the expert banks (8 x 256 x 128 = 2^18
    elements) are stored as quarters and gathered in the share's body, and
    one optimizer step moves ``expert_bias`` by ``coeff * (sign(mean c - c_e)
    - its mean)`` with ``c`` the loads of ALL FOUR devices' tokens, as one
    device with the whole batch reads them. A device's own sequence alone
    gives other signs."""
    from jax.sharding import PartitionSpec as P
    cfg = mimo_v2.MimoV2Config(**dict(
        TINY, d_model=256,
        d_expert=128, experts_held=8, first_expert_held=0, dtype=jnp.float32,
        rows_bound=32, load_balance_coeff=1e-3))
    model, params = mimo_v2.init_params(cfg)
    params = _stirred(params, scale=0.05)
    batch = mimo_v2.synthetic_batch(cfg, batch_size=4, seq_len=32)
    tokens = jnp.asarray(batch["tokens"][:, :-1])
    # the loads one device reads on the whole batch, and on its own sequence
    # (four times over: the whole batch's shape, one compiled program)
    loads = jax.jit(lambda p, t: mimo_v2.expert_loads(model, p, t))
    whole = np.asarray(loads(params, tokens))[0]
    own = np.asarray(loads(params, jnp.tile(tokens[:1], (4, 1))))[0]
    assert whole.sum() == 4 * 32 * 3
    runner = _four_device_runner(
        mimo_v2.make_loss_fn(model), params, batch,
        mimo_v2.make_optimizer(1e-2, cfg.load_balance_coeff))
    state = runner.init(params)
    assert state.params["block_1"]["moe"]["up"].sharding.spec == P("data", None, None)
    assert state.params["block_1"]["attn"]["sink"].sharding.spec == P()
    state, _ = runner.run(state, batch)
    after = jax.device_get(state.params)
    signs = np.sign(whole - whole.mean())      # sign(c_e - mean c), the global batch's
    assert np.abs(signs).max() > 0
    moved = np.asarray(after["block_1"]["moe"]["expert_bias"]) \
        - np.asarray(params["block_1"]["moe"]["expert_bias"])
    np.testing.assert_allclose(
        moved, -cfg.load_balance_coeff * (signs - signs.mean()), atol=1e-7)
    assert (np.sign(own - own.mean()) != signs).any()
    # the banks moved too (AdamW on the quarters), and the sinks
    assert float(jnp.abs(after["block_1"]["moe"]["up"]
                         - params["block_1"]["moe"]["up"]).max()) > 0
    assert float(jnp.abs(after["block_1"]["attn"]["sink"]
                         - params["block_1"]["attn"]["sink"]).max()) > 0


def _four_device_runner(loss_fn, params, batch, optimizer):
    """A runner under ``FullySharded`` on 4 of the host's devices."""
    from autodist_tpu import ResourceSpec
    from autodist_tpu.model_spec import ModelSpec
    from autodist_tpu.parallel.mesh import build_mesh
    from autodist_tpu.parallel.plan import ShardingPlan
    from autodist_tpu.runner import DistributedRunner
    spec = ResourceSpec(resource_info={
        "nodes": [{"address": "localhost", "tpus": 4, "chief": True}],
        "mesh": {"data": 4}})
    model_spec = ModelSpec.from_loss_fn(loss_fn, params, batch)
    strategy = FullySharded().build(model_spec, spec)
    mesh = build_mesh(axes={"data": 4}, devices=jax.devices()[:4])
    return DistributedRunner(strategy, model_spec, loss_fn, optimizer, mesh=mesh,
                             plan=ShardingPlan.from_strategy(strategy, model_spec))
