"""AFMoE / Trinity (``models/afmoe.py``): the system's loss and gradients
against the plain reference the benchmark checks it with on the chip
(``benchmark/reference/afmoe.py``) with every mechanism on, the sigmoid
router's weights by hand, one chip's share of the experts (the shares add up
to the uncut layer; more held rows than the bound take more passes), and the
expert-bias rule, alone and through the normal path. Tiny widths on the CPU mesh; kernels in
interpret mode."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import AutoDist, train
from autodist_tpu.models import afmoe, moe
from autodist_tpu.strategy import AllReduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tests import reference_programs  # noqa: E402

# Two layer kinds, a leading dense layer, a window shorter than the sequence,
# 2 query heads a KV head, the share: experts 2-3 of 8, top-2.
TINY = dict(vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
            layer_types=(afmoe.SLIDING, afmoe.SLIDING, afmoe.FULL),
            n_dense_layers=1, d_ff=96, d_expert=32, n_experts_routed=8,
            experts_held=2, first_expert_held=2, top_k=2, window=8, max_len=64)


def _share(cfg):
    """The expert layer's module as ``afmoe.AfmoeBlock`` builds it."""
    return afmoe.RoutedShare(cfg, cfg.d_expert * cfg.n_shared_experts)


def _rel_l2(a, b):
    leaves = lambda t: jax.tree_util.tree_leaves(t)  # noqa: E731
    num = sum(float(jnp.sum(jnp.square(x - y))) for x, y in zip(leaves(a), leaves(b)))
    return (num / sum(float(jnp.sum(jnp.square(y))) for y in leaves(b))) ** 0.5


def _reference_kwargs(cfg):
    return dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim, layer_types=cfg.layer_types,
                n_dense_layers=cfg.n_dense_layers, top_k=cfg.top_k,
                window=cfg.window, rms_eps=cfg.rms_eps,
                rope_theta=cfg.rope_theta, route_norm=cfg.route_norm,
                route_scale=cfg.route_scale, mup_enabled=cfg.mup_enabled,
                first_expert_held=cfg.first_expert_held)


def _with_bias(params, scale=0.05):
    """The parameters with every ``expert_bias`` leaf drawn, not zero: large
    enough to change choices (sigmoid scores lie within a few hundredths of
    one another at init)."""
    def draw(path, x):
        if path[-1].key != "expert_bias":
            return x
        return scale * jax.random.normal(jax.random.PRNGKey(5), x.shape)
    return jax.tree_util.tree_map_with_path(draw, params)


# The tolerances are OLMoE's, for its reasons (tests/test_olmoe.py): float32
# activations agree to rounding, bfloat16 to parts in a thousand of the loss
# and a few percent of the gradient; a dropped term moves either by far more.
@pytest.mark.parametrize("dtype,attention,fused,loss_tol,grad_tol", [
    (jnp.float32, "dot", False, 1e-5, 1e-5),
    (jnp.float32, "flash", True, 1e-5, 1e-5),
    (jnp.bfloat16, "flash", True, 1e-3, 3e-2),
], ids=["f32-xla", "f32-kernels", "bf16-kernels"])
def test_loss_and_gradients_match_the_plain_reference(dtype, attention, fused,
                                                      loss_tol, grad_tol):
    cfg = afmoe.AfmoeConfig(dtype=dtype, attention_impl=attention,
                            fused_head=fused, **TINY)
    model, params = afmoe.init_params(cfg, jax.random.PRNGKey(1))
    params = _with_bias(params)
    batch = {"tokens": jnp.asarray(
        afmoe.synthetic_batch(cfg, 2, 32, seed=3)["tokens"])}
    loss, grads = jax.jit(jax.value_and_grad(afmoe.make_loss_fn(model)))(
        params, batch)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = reference_programs.value_and_grad(
            "afmoe", **_reference_kwargs(cfg))(params, batch)
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) <= loss_tol
    assert _rel_l2(grads, ref_grads) <= grad_tol
    assert {str(g.dtype) for g in jax.tree_util.tree_leaves(grads)} == {"float32"}
    # the bias takes part in the loss: its gradient is the load error, which
    # sums to zero over the router's width and is not zero
    d_bias = grads["block_1"]["moe"]["expert_bias"]
    assert abs(float(d_bias.sum())) < 1e-6 and float(jnp.abs(d_bias).max()) > 0


def test_the_tiny_stack_has_the_parameters_the_equations_name():
    cfg = afmoe.AfmoeConfig(**TINY)
    _, params = afmoe.init_params(cfg)
    d, wide, narrow = 64, 4 * 16, 2 * 16
    attention = 3 * d * wide + 2 * d * narrow + 2 * 16      # q, gate, o; k, v; QK norms
    dense = attention + 4 * d + 3 * d * 96
    expert = attention + 4 * d + 3 * d * 32 + d * 8 + 8 + 2 * 3 * d * 32
    want = dense + 2 * expert + 2 * 256 * d + d
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == want
    assert set(params["block_0"]) == {"attn", "ln_in", "ln_post_attn",
                                      "ln_pre_mlp", "ln_post_mlp", "mlp"}
    assert set(params["block_2"]["moe"]) == {"router", "expert_bias", "gate",
                                             "up", "down", "shared"}
    assert params["block_2"]["moe"]["gate"].shape == (2, d, 32)
    assert params["block_2"]["moe"]["router"].shape == (d, 8)


def test_sigmoid_router_weights_by_hand_for_one_token():
    scores = jnp.asarray([[0.9, 0.1, 0.5, 0.6, 0.2, 0.3, 0.8, 0.4]])
    bias = jnp.asarray([-0.5, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.45])
    route = moe.sigmoid_topk_route(scores, 3, bias, route_norm=True,
                                   route_scale=2.826)
    # chosen by score + bias: expert 7 (0.85), 6 (0.8), 3 (0.6); expert 0 falls
    # to 0.4. The weights are the scores WITHOUT the bias, over their sum.
    np.testing.assert_array_equal(route.indices, [[7, 6, 3]])
    np.testing.assert_allclose(
        route.weights, [[2.826 * s / (0.4 + 0.8 + 0.6) for s in (0.4, 0.8, 0.6)]],
        rtol=1e-6)
    np.testing.assert_array_equal(route.group_sizes, [0, 0, 0, 1, 0, 0, 1, 1])
    plain = moe.sigmoid_topk_route(scores, 3, None, route_norm=False,
                                   route_scale=1.0)
    np.testing.assert_array_equal(plain.indices, [[0, 6, 3]])
    np.testing.assert_allclose(plain.weights, [[0.9, 0.8, 0.6]], rtol=1e-6)
    # the bias steers the choice and takes no gradient through it
    d_bias = jax.grad(lambda b: moe.sigmoid_topk_route(
        scores, 3, b, route_norm=True, route_scale=1.0).weights.sum())(bias)
    np.testing.assert_array_equal(d_bias, np.zeros(8))
    # the share: experts 6-7 held, sorted first, the third row an absent expert's
    share = moe.sigmoid_topk_route(scores, 3, bias, first_expert=6, n_held=2)
    np.testing.assert_array_equal(share.group_sizes, [1, 1])
    np.testing.assert_array_equal(share.perm, [1, 0, 2])


def _layer_inputs(tokens=24, d=16, w=24, experts=8):
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(keys[0], (tokens, d))
    scores = jax.nn.sigmoid(jax.random.normal(keys[1], (tokens, experts)))
    bias = 0.3 * jax.random.normal(keys[5], (experts,))
    gate, up = (jax.random.normal(key, (experts, d, w)) * 0.3 for key in keys[2:4])
    down = jax.random.normal(keys[4], (experts, w, d)) * 0.3
    return x, scores, bias, (gate, up, down)


def test_the_shares_add_up_to_the_uncut_layer():
    """What the guide asks of a share: the parts of the result that all the
    shares give (experts 0-1, 2-3, 4-5, 6-7 of 8), with what every chip
    computes alike, the shared expert, counted once, add up to what the uncut
    reference gives for the whole layer. The system's layer module on each
    share's slice of one parameter tree; the reference on the whole tree."""
    from benchmark.reference import afmoe as reference
    cfg = afmoe.AfmoeConfig(dtype=jnp.float32, **dict(
        TINY, experts_held=8, first_expert_held=0))
    d, tokens = cfg.d_model, 40
    whole = _share(cfg).init(
        jax.random.PRNGKey(2), jnp.zeros((1, 4, d)))["params"]
    whole = _with_bias(whole, scale=0.2)
    h = jax.random.normal(jax.random.PRNGKey(3), (1, tokens, d))

    total = 0.0
    for first in (0, 2, 4, 6):
        share_cfg = afmoe.AfmoeConfig(dtype=jnp.float32, **dict(
            TINY, experts_held=2, first_expert_held=first))
        params = dict(whole, **{name: whole[name][first:first + 2]
                                for name in ("gate", "up", "down")})
        out, _ = _share(share_cfg).apply(
            {"params": params}, h)
        total = total + out
    with jax.default_matmul_precision("highest"):
        shared = reference.gated_mlp(h, whole["shared"])
        routed, _ = reference.mixture(
            h.reshape(tokens, d), whole, top_k=cfg.top_k,
            route_norm=cfg.route_norm, route_scale=cfg.route_scale,
            first_expert_held=0)
    # four shares each added the shared expert: counted once
    np.testing.assert_allclose(total - 3 * shared,
                               shared + routed.reshape(1, tokens, d),
                               rtol=1e-4, atol=1e-5)
    # and the whole bank in one layer is the same uncut result
    uncut, _ = _share(cfg).apply({"params": whole}, h)
    np.testing.assert_allclose(uncut, shared + routed.reshape(1, tokens, d),
                               rtol=1e-4, atol=1e-5)


def _dense_share(x, scores, gate, up, down, bias, *, k, first, scale):
    """The held experts' part by a 0/1 choice mask: every held expert on every
    token, weighted where it is among the token's top k."""
    held = up.shape[0]
    choice = scores + bias
    kth = jnp.sort(choice, axis=-1)[:, scores.shape[1] - k]
    w = jnp.where(choice >= kth[:, None], scores, 0.0)
    w = scale * w / w.sum(axis=-1, keepdims=True)
    hidden = jnp.einsum("td,edw->tew", x, up)
    if gate is None:                # the relu2 form: two banks, no gate
        hidden = jnp.square(jax.nn.relu(hidden))
    else:
        hidden = jax.nn.silu(jnp.einsum("td,edw->tew", x, gate)) * hidden
    every = jnp.einsum("tew,ewd->ted", hidden, down)
    return jnp.einsum("te,ted->td", w[:, first:first + held], every)


FORMS = pytest.mark.parametrize("form", list(moe.EXPERT_FORMS))


def _bank_of(form, bank):
    """``(gate, up, down)`` as the form takes it: no gate bank under relu2."""
    return bank if form == "gated_silu" else [None, *bank[1:]]


def _value_and_grads(fn, args, argnums, weight=1.0):
    """``fn(*args)`` and the gradients of ``(fn(*args) * weight).sum()`` for
    the ``argnums`` that hold an array (a form without a gate has None in its
    place): one compiled program, one forward."""
    argnums = tuple(i for i in argnums if args[i] is not None)

    def summed(*a):
        y = fn(*a)
        return (y * weight).sum(), y
    grads, y = jax.jit(jax.grad(summed, argnums=argnums, has_aux=True))(*args)
    return y, grads


def _all_avals(jaxpr):
    """Every output of every equation, sub-programs (loops, custom
    derivatives) included."""
    for eqn in jaxpr.eqns:
        yield from (v.aval for v in eqn.outvars)
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (tuple, list)) else [param]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _all_avals(sub)


@FORMS
@pytest.mark.parametrize("bound", [None, 56, 16], ids=["one-pass-T*k", "bound-56", "bound-16"])
def test_a_share_equals_its_experts_under_a_mask_and_keeps_absent_rows_out(bound,
                                                                           form):
    """Experts 2-4 of 8 held, top-3: in one pass over T*k rows, in buffers of
    56 rows (the held rows fit one pass), and of 16 (they need several); the
    gated-SiLU expert over three banks and the relu2 one over two."""
    x, scores, bias, bank = _layer_inputs()
    tokens, k, first, held = x.shape[0], 3, 2, 3
    route = functools.partial(moe.sigmoid_topk_route, route_norm=True,
                              route_scale=2.0)
    mine = _bank_of(form, [b[first:first + held] for b in bank])
    share = lambda x, scores, gate, up, down, bias: moe.routed_experts(  # noqa: E731
        x, scores, gate, up, down, bias, top_k=k, route=route,
        first_expert=first, rows_bound=bound, form=form)[0]
    dense = functools.partial(_dense_share, k=k, first=first, scale=2.0)

    args = (x, scores, *mine, bias)
    held_rows = int(route(scores, k, bias, first_expert=first,
                          n_held=held).group_sizes.sum())
    assert 16 < held_rows <= 56 < tokens * k
    y, got = _value_and_grads(share, args, (0, 1, 2, 3, 4))
    want_y, want = _value_and_grads(dense, args, (0, 1, 2, 3, 4))
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    assert len(got) == (5 if form == "gated_silu" else 4)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5)
    if bound is None:
        return
    # The buffers hold the bound's rows, not tokens x k: nothing two-
    # dimensional in the program, forward or backward, has tokens x k rows.
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: share(*a).sum(),
                                    argnums=(0, 3, 4)))(*args)
    rows = {aval.shape[0] for aval in _all_avals(jaxpr.jaxpr)
            if len(aval.shape) == 2 and aval.shape[1] > 1}
    assert tokens * k not in rows and bound in rows


def test_more_held_rows_than_the_bound_take_more_passes_and_nothing_is_dropped():
    """Every token's two choices land on the two experts held: 2T held rows
    against a bound of T / 2, so four passes over the same buffers; with all
    rows on ONE held expert the other's group is empty. The result is the
    dense one either way: no row is dropped and none fails. (ISSUE 29 asked
    for a NaN past the bound; on the chip a randomly initialised router sent
    up to half of all rows to one rank, so the bound became a pass: PERF.md
    §6.)"""
    x, scores, _, bank = _layer_inputs()
    tokens, k = x.shape[0], 2
    mine = [b[4:6] for b in bank]
    dense = functools.partial(_dense_share, k=k, first=4, scale=1.0)
    both = jnp.zeros(8).at[jnp.asarray([4, 5])].set(10.0)   # everyone chooses 4 and 5
    one = jnp.zeros(8).at[4].set(10.0).at[0].set(9.0)       # everyone chooses 4 (and 0)

    def with_gradients(layer):
        """y, what ``layer`` returns beside it, and the gradients of
        ``y.sum()`` for x and the gate bank: one compiled program, the bias
        an argument."""
        def summed(x, gate, bias):
            y, *rest = layer(x, gate, bias)
            return y.sum(), (y, *rest)

        @jax.jit
        def run(bias):
            grads, outs = jax.grad(summed, argnums=(0, 1), has_aux=True)(
                x, mine[0], bias)
            return (*outs, grads)
        return run

    dense_of = with_gradients(
        lambda x, gate, bias: (dense(x, scores, gate, *mine[1:], bias),))
    for bound in (tokens // 2, tokens, tokens * k, None):
        share_of = with_gradients(lambda x, gate, bias: moe.routed_experts(
            x, scores, gate, *mine[1:], bias, top_k=k,
            route=moe.sigmoid_topk_route, first_expert=4,
            rows_bound=bound))  # noqa: B023
        for bias, want_sizes in ((both, [tokens, tokens]), (one, [tokens, 0])):
            y, sizes, got = share_of(bias)
            want_y, want = dense_of(bias)
            np.testing.assert_array_equal(sizes, want_sizes)
            np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
            for g, r in zip(got, want):
                np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5)
    # none of the held experts chosen by anyone: no pass at all, zeros
    nobody = jnp.zeros(8).at[jnp.asarray([0, 1])].set(10.0)
    y, sizes = jax.jit(lambda bias: moe.routed_experts(
        x, scores, *mine, bias, top_k=k, route=moe.sigmoid_topk_route,
        first_expert=4, rows_bound=tokens // 2))(nobody)
    assert int(sizes.sum()) == 0 and not np.asarray(y).any()
    # and the model's loss stays finite under a bound far below the held rows
    cfg = afmoe.AfmoeConfig(dtype=jnp.float32, **dict(TINY, rows_bound=4))
    model, params = afmoe.init_params(cfg)
    batch = {"tokens": jnp.asarray(
        afmoe.synthetic_batch(cfg, 2, 32, seed=3)["tokens"])}
    params = jax.tree_util.tree_map_with_path(
        lambda path, p: jnp.zeros(8).at[jnp.asarray([2, 3])].set(10.0)
        if path[-1].key == "expert_bias" else p, params)
    loss, grads = jax.jit(jax.value_and_grad(afmoe.make_loss_fn(model)))(
        params, batch)
    wide = afmoe.Afmoe(afmoe.AfmoeConfig(dtype=jnp.float32, **TINY))
    np.testing.assert_allclose(
        loss, jax.jit(afmoe.make_loss_fn(wide))(params, batch), rtol=1e-6)
    assert all(np.isfinite(g).all() for g in jax.tree_util.tree_leaves(grads))


def _kernels_and_loops(jaxpr, inside=False):
    """``(kernel name, inside a loop?)`` of every Pallas call and ``("while",
    first index)`` of every loop, sub-programs included (a ``fori_loop`` over
    a traced bound carries its index first)."""
    for eqn in jaxpr.eqns:
        loop = eqn.primitive.name == "while"
        if eqn.primitive.name == "pallas_call":
            yield str(eqn.params["name"]), inside
            continue        # a kernel's own loops are not passes
        elif loop:
            first = eqn.invars[eqn.params["cond_nconsts"]
                               + eqn.params["body_nconsts"]]
            yield "while", int(first.val)
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (tuple, list)) else [param]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _kernels_and_loops(sub, inside or loop)


@pytest.mark.parametrize("what,outside,inside", [
    ("moe_gmm_fwd", 3, 6), ("moe_gmm_bwd_dx", 3, 3), ("moe_gmm_bwd_dw", 3, 3),
    ("moe_rows_combine", 2, 3), ("moe_rows_gather", 0, 0),
    ("while", None, [1, 1])])
def test_the_first_pass_runs_once_and_only_the_passes_past_it_recompute(
        what, outside, inside):
    """The gradient program of a share whose bound is below ``T*k``: pass 0's
    three products and their six transposes sit outside every loop, once (its
    forward is not run again for the backward); the two loops, the forward's
    and the transpose's, start at pass 1, and the transpose's holds the three
    products a second time beside their transposes. A pass adds a token's
    rows up in a kernel twice, in the combine and in the dispatch's transpose
    (so twice outside, once in the forward's loop, twice in the transpose's);
    its two gathers are XLA's."""
    x, scores, bias, bank = _layer_inputs()
    share = lambda x, gate, up, down: moe.routed_experts(  # noqa: E731
        x, scores, gate, up, down, bias, top_k=3,
        route=moe.sigmoid_topk_route, first_expert=2, rows_bound=16)[0]
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: share(*a).sum(),
                                    argnums=(0, 1, 2, 3)))(
        x, *(b[2:5] for b in bank))
    found = list(_kernels_and_loops(jaxpr.jaxpr))
    if what == "while":
        assert [first for name, first in found if name == "while"] == inside
        return
    calls = [looped for name, looped in found if name == what]
    assert calls.count(False) == outside and calls.count(True) == inside


def test_two_layers_and_their_loops_trace_the_pass_at_most_twice(monkeypatch):
    """What a warm start pays for the share is tracing: pass 0, the forward's
    loop and the transpose's loop of every expert layer with the same shapes
    run the pass's Python body, and so its three kernels' tracing, twice
    between them (JAX keeps one trace for the calls of the forward and one
    for those made while it transposes), not three times a layer. Shapes no
    other test of this file uses."""
    x, scores, bias, bank = _layer_inputs(tokens=20, d=16, w=40)
    calls = []
    inner = moe._expert_mlps
    monkeypatch.setattr(moe, "_expert_mlps",
                        lambda *a: calls.append(1) or inner(*a))
    share = lambda x, gate, up, down: moe.routed_experts(  # noqa: E731
        x, scores, gate, up, down, bias, top_k=3,
        route=moe.sigmoid_topk_route, first_expert=2, rows_bound=16)[0]
    two_layers = lambda x, *bank: share(share(x, *bank), *bank).sum()  # noqa: E731
    jax.make_jaxpr(jax.grad(two_layers, argnums=(0, 1, 2, 3)))(
        x, *(b[2:5] for b in bank))
    assert 1 <= len(calls) <= 2


# Held experts 4-5 of 8, top-2, 24 tokens: (the two experts every token
# chooses, bound, passes): 4 and 5 (48 held rows), 4 and 0 (24), 0 and 1 (none).
_ROUTINGS = {"1-pass": ((4, 0), 36, 1), "2-passes": ((4, 5), 24, 2),
             "4-passes": ((4, 5), 12, 4), "no-held-rows": ((0, 1), 12, 0)}


def _bias_on(experts):
    return jnp.zeros(8).at[jnp.asarray(experts)].set(jnp.asarray([10.0, 9.0]))


@FORMS
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("routing", ["1-pass", "2-passes", "4-passes"])
def test_kept_and_recomputed_passes_give_the_dense_shares_gradients(
        routing, dtype, tol, form):
    """y and the gradients of x, the router's scores and the banks (three
    gated, two relu2) through :func:`moe._held_passes` (every bound is below
    ``T*k``), pass 0 transposed from what it kept and the others recomputed,
    against the dense share in float32 on the same (rounded) rows."""
    x, scores, _, bank = _layer_inputs()
    chosen, bound, _ = _ROUTINGS[routing]
    bias, mine = _bias_on(chosen), _bank_of(form, [b[4:6] for b in bank])
    x = x.astype(dtype)
    if form == "relu2" and dtype == jnp.bfloat16:
        tol = 2 * tol           # the square doubles the hidden row's rounding
    share = lambda x, scores, *bank: moe.routed_experts(  # noqa: E731
        x, scores, *bank, bias, top_k=2, route=moe.sigmoid_topk_route,
        first_expert=4, rows_bound=bound, form=form)[0]
    dense = lambda x, scores, *bank: _dense_share(  # noqa: E731
        x.astype(jnp.float32), scores, *bank, bias, k=2, first=4, scale=1.0)
    assert bound < x.shape[0] * 2
    target = jax.random.normal(jax.random.PRNGKey(7), x.shape)
    y, got = _value_and_grads(share, (x, scores, *mine), range(5), target)
    want_y, want = _value_and_grads(dense, (x, scores, *mine), range(5), target)
    np.testing.assert_allclose(y, want_y, rtol=tol, atol=tol)
    assert got[0].dtype == dtype and got[2].dtype == jnp.float32
    for g, r in zip(got, want):
        assert _rel_l2(g.astype(jnp.float32), r.astype(jnp.float32)) <= tol


@pytest.mark.parametrize("routing", list(_ROUTINGS))
def test_the_layer_sows_the_passes_its_held_rows_took(routing):
    """Beside ``load``: ``ceil(held rows / rows_bound)`` of the step's
    routing, 0 where no row is held (pass 0 runs then too, over nothing)."""
    chosen, bound, passes = _ROUTINGS[routing]
    bias = _bias_on(chosen)
    cfg = afmoe.AfmoeConfig(dtype=jnp.float32, **dict(
        TINY, first_expert_held=4, rows_bound=bound))
    layer = _share(cfg)
    params = layer.init(jax.random.PRNGKey(2), jnp.zeros((1, 4, 64)))["params"]
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 24, 64))
    _, sown = layer.apply({"params": dict(params, expert_bias=bias)}, h,
                          mutable=["intermediates"])
    (took,), (load,) = (sown["intermediates"][k] for k in ("passes", "load"))
    assert took.dtype == jnp.int32 and int(took) == passes
    assert -(-int(load[4:6].sum()) // bound) == passes


def test_the_balancing_rule_alone_levels_a_random_routers_loads():
    """``balance_expert_bias``: no weight moves, every expert layer's bias
    does, and the loads' spread over the router's width falls."""
    cfg = afmoe.AfmoeConfig(dtype=jnp.float32, **dict(
        TINY, experts_held=8, first_expert_held=0, top_k=2))
    model, params = afmoe.init_params(cfg, jax.random.PRNGKey(4))
    tokens = jnp.asarray(afmoe.synthetic_batch(cfg, 8, 32, seed=1)["tokens"])[:, :-1]
    before = afmoe.expert_loads(model, params, tokens)
    assert before.shape == (2, 8) and float(before.sum()) == 2 * 8 * 32 * 2
    balanced = afmoe.balance_expert_bias(model, params, [tokens],
                                         np.geomspace(0.05, 0.001, 40))
    after = afmoe.expert_loads(model, balanced, tokens)
    assert float(after.std(axis=1).max()) < 0.5 * float(before.std(axis=1).min())
    moved = jax.tree_util.tree_map(lambda a, b: float(jnp.abs(a - b).max()),
                                   params, balanced)
    for path, delta in jax.tree_util.tree_leaves_with_path(moved):
        assert (delta > 0) == (path[-1].key == "expert_bias"), path
    for block in ("block_1", "block_2"):
        assert abs(float(balanced[block]["moe"]["expert_bias"].sum())) < 1e-5


def test_a_step_through_the_normal_path_moves_the_bias_by_the_rule():
    """``AutoDist(...).function``-style session and ``train()``, nothing on
    the side: after one optimizer step every expert-bias leaf has moved by
    ``coeff * (sign(mean c - c_e) - its mean)``, with ``c`` the loads of the
    step's batch under the starting parameters, and the update sums to zero;
    the other leaves moved by AdamW; three steps and the loss falls."""
    cfg = afmoe.AfmoeConfig(dtype=jnp.bfloat16, attention_impl="flash",
                            fused_head=True, load_balance_coeff=1e-3, **TINY)
    model, params = afmoe.init_params(cfg)
    params = _with_bias(params)
    batch = afmoe.synthetic_batch(cfg, batch_size=8, seq_len=32)
    loss_fn = afmoe.make_loss_fn(model)
    optimizer = afmoe.make_optimizer(1e-2, cfg.load_balance_coeff)
    # the load error the rule reads is the gradient of the loss's bias term
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
        assert abs(moved.sum()) < 1e-6
        # an over-loaded expert's bias falls, an under-loaded one's rises
        assert (moved[signs > 0] < 0).all() and (moved[signs < 0] > 0).all()
    assert float(jnp.abs(after["block_0"]["mlp"]["up"]["kernel"]
                         - params["block_0"]["mlp"]["up"]["kernel"]).max()) > 0
    _, losses = one_run(3)
    assert len(losses) >= 2 and all(np.isfinite(losses))
    assert losses[-1] < losses[0]
