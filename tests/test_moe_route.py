"""The routing of the dropless mixtures makes no by-index scalar access.

``models/moe.py`` ``sigmoid_topk_route`` and ``topk_route`` pick the chosen
scores with a select against the router's width where they called
``take_along_axis`` or kept ``top_k``'s own values, and ``_sorted_route`` reads
the sorted keys from the sort itself where it read ``flat[perm]`` and makes
``inv_perm`` by a second sort where it scattered. The forms they replaced are
kept here as the plain reference: the same chosen experts, weights, groups and
order, and the same gradient, to the bit, primitive by primitive and inside
one compiled program (a barrier keeps the normaliser's sum over ``k`` apart
from the select's sum over the width, which the compiler would fold together
and add up in another order).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.interpreters import partial_eval as pe

from autodist_tpu import telemetry
from autodist_tpu.models import moe

TOKENS, WIDTH, TOP_K = 96, 16, 4
SHARE = dict(first_expert=4, n_held=4)


def route_before(scores, k, bias=None, *, sigmoid=True, route_norm=True,
                 route_scale=1.0, route_eps=1e-20, first_expert=0, n_held=None):
    """Both routers as they stood before PR 51: ``take_along_axis`` for the
    sigmoid router's chosen scores and ``top_k``'s own values for the softmax
    router's, ``argsort`` and then ``flat[perm]`` for the keys
    ``searchsorted`` reads, a scatter for ``inv_perm``."""
    choice = scores if bias is None else scores + jax.lax.stop_gradient(bias)
    weights, indices = jax.lax.top_k(choice, k)
    if sigmoid:
        weights = jnp.take_along_axis(scores, indices, axis=-1)
        if route_norm:
            weights = weights / (weights.sum(axis=-1, keepdims=True) + route_eps)
        weights = weights * route_scale
    width = scores.shape[1]
    n_held = width if n_held is None else n_held
    flat = indices.reshape(-1).astype(jnp.int32)
    if n_held != width:
        local = flat - first_expert
        flat = jnp.where((local >= 0) & (local < n_held), local, n_held)
    perm = jnp.argsort(flat, stable=True).astype(jnp.int32)
    rows = jnp.arange(flat.size, dtype=jnp.int32)
    inv_perm = jnp.zeros_like(perm).at[perm].set(rows, unique_indices=True)
    ends = jnp.searchsorted(flat[perm], jnp.arange(n_held, dtype=jnp.int32),
                            side="right").astype(jnp.int32)
    return moe.Route(indices.astype(jnp.int32), weights,
                     jnp.diff(ends, prepend=0), perm, inv_perm)


def _scores(sigmoid: bool):
    logits = jax.random.normal(jax.random.PRNGKey(0), (TOKENS, WIDTH))
    return jax.nn.sigmoid(logits) if sigmoid else jax.nn.softmax(logits)


def _bias():
    return 0.3 * jax.random.normal(jax.random.PRNGKey(1), (WIDTH,))


# (the router, a bias, the experts held): topk_route takes no bias
CASES = [
    pytest.param(True, False, {}, id="sigmoid-whole"),
    pytest.param(True, True, {}, id="sigmoid-bias-whole"),
    pytest.param(True, False, SHARE, id="sigmoid-share"),
    pytest.param(True, True, SHARE, id="sigmoid-bias-share"),
    pytest.param(True, True, dict(SHARE, route_norm=False, route_scale=2.5),
                 id="sigmoid-bias-share-unnormalised"),
    pytest.param(False, False, {}, id="softmax-whole"),
    pytest.param(False, False, SHARE, id="softmax-share"),
]


def _routers(sigmoid, with_bias, options):
    bias = _bias() if with_bias else None
    now = moe.sigmoid_topk_route if sigmoid else moe.topk_route
    return (lambda s: now(s, TOP_K, bias, **options),
            lambda s: route_before(s, TOP_K, bias, sigmoid=sigmoid, **options))


COMPILED = pytest.mark.parametrize("compiled", [False, True],
                                   ids=["by-primitive", "one-program"])


@COMPILED
@pytest.mark.parametrize("sigmoid,with_bias,options", CASES)
def test_the_route_is_the_indexed_forms_to_the_bit(sigmoid, with_bias, options,
                                                   compiled):
    now, before = _routers(sigmoid, with_bias, options)
    if compiled:
        now, before = jax.jit(now), jax.jit(before)
    got, want = now(_scores(sigmoid)), before(_scores(sigmoid))
    for field, a, b in zip(moe.Route._fields, got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, field
        np.testing.assert_array_equal(a, b, err_msg=field)
    held = options.get("n_held", WIDTH)
    assert got.group_sizes.shape == (held,)
    assert int(got.group_sizes.sum()) == int(np.sum(
        (np.asarray(got.indices) >= options.get("first_expert", 0))
        & (np.asarray(got.indices) < options.get("first_expert", 0) + held)))


@COMPILED
@pytest.mark.parametrize("sigmoid,with_bias,options", CASES)
def test_the_routes_gradient_is_the_indexed_forms_to_the_bit(sigmoid, with_bias,
                                                             options, compiled):
    now, before = _routers(sigmoid, with_bias, options)
    ct = jax.random.normal(jax.random.PRNGKey(2), (TOKENS, TOP_K))
    wrap = jax.jit if compiled else (lambda f: f)
    got, want = (wrap(jax.grad(lambda s, f=f: jnp.sum(f(s).weights * ct)))(
        _scores(sigmoid)) for f in (now, before))
    assert float(jnp.abs(want).max()) > 0
    np.testing.assert_array_equal(got, want)


def indexed_scalar_ops(jaxpr, at_least: int) -> list:
    """The ``gather`` / ``scatter`` / ``scatter-add`` equations of ``jaxpr``
    (and of every jaxpr inside it but a kernel's) that move ``at_least``
    scalars or more one by one: every slice of one element."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "gather":
            if (all(s == 1 for s in eqn.params["slice_sizes"])
                    and eqn.outvars[0].aval.size >= at_least):
                found.append(eqn)
        elif name.startswith("scatter"):
            if (not eqn.params["dimension_numbers"].update_window_dims
                    and eqn.invars[2].aval.size >= at_least):
                found.append(eqn)
        if name == "pallas_call":
            continue
        for inner in jax.core.jaxprs_in_params(eqn.params):
            found += indexed_scalar_ops(inner, at_least)
    return found


def _live(fn, *args):
    """``fn``'s jaxpr without what none of its results reads."""
    closed = jax.make_jaxpr(fn)(*args)
    return pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.jaxpr.outvars))[0]


def _share_reads(route: moe.Route):
    # ``routed_experts`` reads no ``inv_perm`` of a share
    return route.indices, route.weights, route.group_sizes, route.perm


@pytest.mark.parametrize("what", ["forward", "gradient"])
@pytest.mark.parametrize("form,count", [("now", 0), ("before", None)])
def test_a_shares_routing_moves_no_scalar_by_index(what, form, count):
    """PERF.md section 7, "Open after PR 34" (1), turned round: the jaxpr of
    a share's routing held ``take_along_axis``'s gather and ``flat[perm]``,
    its gradient's a scatter-add beside them, each over the ``T*k`` routed
    choices; now neither holds any."""
    bias, ct = _bias(), jnp.ones((TOKENS, TOP_K))
    route = functools.partial(
        moe.sigmoid_topk_route if form == "now" else route_before,
        k=TOP_K, bias=bias, **SHARE)
    fn = {"forward": lambda s: _share_reads(route(s)),
          "gradient": jax.grad(lambda s: jnp.sum(route(s).weights * ct))}[what]
    ops = [e.primitive.name for e in indexed_scalar_ops(
        _live(fn, _scores(True)), TOKENS * TOP_K)]
    if count is None:       # the walker finds what the old forms made
        assert sorted(ops) == {"forward": ["gather", "gather"],
                               "gradient": ["gather", "scatter-add"]}[what]
    else:
        assert len(ops) == count, ops


def _layer(whole: bool):
    """One expert layer through ``routed_experts``, a single pass: ``(fn of
    x, scores and the banks, its arguments)``."""
    d, w = 8, 8
    held = WIDTH if whole else SHARE["n_held"]
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(keys[0], (TOKENS, d))
    bank = [0.1 * jax.random.normal(k, shape) for k, shape in zip(
        keys[1:], ((held, d, w), (held, d, w), (held, w, d)))]
    if whole:
        return (lambda x, s, *b: moe.routed_experts(x, s, *b, top_k=TOP_K)[0],
                (x, _scores(False), *bank))
    return (lambda x, s, *b: moe.routed_experts(
        x, s, *b, _bias(), top_k=TOP_K, route=moe.sigmoid_topk_route,
        first_expert=SHARE["first_expert"])[0], (x, _scores(True), *bank))


@pytest.mark.parametrize("whole", [True, False], ids=["whole-bank", "share"])
def test_the_gauge_counts_the_scalar_moves_the_layers_gradient_still_makes(whole):
    """``moe.route.indexed_scalar_ops`` is what the jaxpr of the layer's
    gradient holds: the gathers, scatters and scatter-adds that move a
    layer's routed choices one scalar at a time (the rows' gathers move
    ``d`` a slice and are not among them)."""
    fn, args = _layer(whole)
    telemetry.gauge("moe.route.indexed_scalar_ops").set(-1)
    live = _live(jax.value_and_grad(lambda *a: fn(*a).sum(),
                                    argnums=tuple(range(len(args)))), *args)
    found = [e.primitive.name for e in indexed_scalar_ops(live, TOKENS * TOP_K)]
    assert telemetry.gauge("moe.route.indexed_scalar_ops").value == len(found), found
