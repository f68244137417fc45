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
from benchmark.reference import bailing_hybrid as reference

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


# ------------------------------------------------------ the grouped choice
#
# ``group_limited`` / ``sigmoid_topk_route(n_group, topk_group)`` (PR 52):
# against plain ``top_k`` calls (``benchmark/reference/bailing_hybrid.py``
# ``grouped_choice``), ties included; one group is the choice as it was; the
# weights carry no bias; one chip's share sorts the held rows first.

def _grouped_scores(tokens=96, width=32, seed=0, levels=None):
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(seed),
                                              (tokens, width)))
    if levels:      # few distinct values: ties inside groups and between them
        scores = jnp.round(scores * levels) / levels
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1), (width,))
    return scores, (jnp.round(bias * 8) / 8 if levels else bias)


def _chosen_mask(route, width):
    return np.asarray(jax.nn.one_hot(route.indices, width).sum(axis=1) > 0)


@pytest.mark.parametrize("n_group,topk_group,top_k,levels", [
    (4, 2, 4, None), (8, 4, 8, None), (8, 1, 3, None), (4, 4, 6, None),
    (4, 2, 4, 4), (8, 4, 8, 3)],
    ids=["4-2-4", "8-4-8", "one-group-kept", "all-groups-kept", "ties-4",
         "ties-3"])
def test_the_grouped_choice_is_the_plain_top_k_calls(n_group, topk_group, top_k,
                                                     levels):
    scores, bias = _grouped_scores(levels=levels)
    route = moe.sigmoid_topk_route(scores, top_k, bias, n_group=n_group,
                                   topk_group=topk_group, route_scale=2.5)
    want = np.asarray(reference.grouped_choice(scores + bias, top_k, n_group,
                                               topk_group))
    np.testing.assert_array_equal(_chosen_mask(route, 32), want)
    assert want.sum(axis=1).tolist() == [top_k] * 96
    # every chosen expert lies in one of topk_group groups
    groups = np.asarray(route.indices) // (32 // n_group)
    assert max(len(set(row)) for row in groups) <= topk_group
    # the weights are the scores without the bias, normalised and scaled
    picked = np.take_along_axis(np.asarray(scores), np.asarray(route.indices), 1)
    np.testing.assert_allclose(
        route.weights, 2.5 * picked / (picked.sum(axis=1, keepdims=True) + 1e-20),
        rtol=1e-6)
    assert telemetry.gauge("moe.route.groups").value == n_group
    assert telemetry.gauge("moe.route.groups_kept").value == topk_group


def test_one_group_is_the_choice_as_it_was():
    scores, bias = _grouped_scores()
    plain = moe.sigmoid_topk_route(scores, 4, bias)
    for same in (moe.sigmoid_topk_route(scores, 4, bias, n_group=1, topk_group=1),
                 moe.sigmoid_topk_route(scores, 4, bias, n_group=4, topk_group=4)):
        for a, b in zip(plain, same):
            np.testing.assert_array_equal(a, b)
    text = lambda **groups: jax.jit(  # noqa: E731
        lambda s, b: moe.sigmoid_topk_route(s, 4, b, **groups)).lower(
            scores, bias).as_text()
    assert text() == text(n_group=1, topk_group=1)
    assert text() != text(n_group=4, topk_group=2)


def test_a_group_scores_by_its_two_best_and_ties_go_to_the_lower_group():
    # groups of 4: group 1 has the single best expert, group 2 the best pair
    choice = jnp.asarray([[0.1, 0.1, 0.1, 0.1, 0.9, 0.0, 0.0, 0.0,
                           0.6, 0.6, 0.0, 0.0, 0.3, 0.3, 0.3, 0.3]])
    kept = moe.group_limited(choice, 4, 1)
    assert np.isfinite(np.asarray(kept))[0].tolist() == [False] * 8 + [True] * 4 \
        + [False] * 4
    # groups 0 and 3 tie at 0.6 behind group 2 and group 1: the lower stays
    kept = moe.group_limited(choice.at[0, :4].set(0.3), 4, 3)
    assert np.isfinite(np.asarray(kept))[0].tolist() == [True] * 12 + [False] * 4
    with pytest.raises(ValueError, match="equal groups"):
        moe.group_limited(choice, 3, 1)


def test_the_choice_takes_no_gradient_and_the_weights_do():
    scores, bias = _grouped_scores()

    def total(scores, bias):
        return moe.sigmoid_topk_route(scores, 4, bias, n_group=4,
                                      topk_group=2).weights[:, 0].sum()

    d_scores, d_bias = jax.grad(total, argnums=(0, 1))(scores, bias)
    assert np.any(d_scores) and not np.any(d_bias)


def test_a_share_of_a_grouped_router_sorts_its_held_rows_first():
    scores, bias = _grouped_scores()
    whole = moe.sigmoid_topk_route(scores, 4, bias, n_group=4, topk_group=2)
    share = moe.sigmoid_topk_route(scores, 4, bias, n_group=4, topk_group=2,
                                   first_expert=8, n_held=8)
    np.testing.assert_array_equal(share.indices, whole.indices)
    np.testing.assert_array_equal(share.group_sizes, whole.group_sizes[8:16])
    held = int(share.group_sizes.sum())
    flat = np.asarray(whole.indices).reshape(-1)
    assert sorted(np.asarray(share.perm[:held]).tolist()) == \
        np.nonzero((flat >= 8) & (flat < 16))[0].tolist()
