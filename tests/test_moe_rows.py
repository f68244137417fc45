"""The two row-movement kernels of one chip's share (``ops/moe_rows.py``)
against XLA's gather and scatter-add, and the share's pass built on them
against the XLA formulation it replaced, kept here as the reference.

CPU, pallas interpret mode: the arithmetic and the index walk, not what
Mosaic accepts (``tests/test_chip_compile.py`` compiles both kernels for a
described v5e at the cells' shapes).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.models import moe
from autodist_tpu.ops import moe_rows

T, R, D, TOP_K = 21, 19, 256, 4       # neither a multiple of the 8-row tiles


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    """Tiles of 8 rows and fetches of 4, so that 19 rows over 21 tokens walk
    several tiles, a ragged last one and several fetches a tile."""
    monkeypatch.setattr(moe_rows, "ROW_TILE", 8)
    monkeypatch.setattr(moe_rows, "TOKEN_TILE", 8)
    monkeypatch.setattr(moe_rows, "FETCH_ROWS", 4)


def _tokens(kind: str):
    """``[R]`` tokens of the compacted rows. ``spread``: random. ``piled``:
    token 5 holds ``TOP_K`` rows (every slot held), tokens 0-3 and 9-20 none."""
    if kind == "spread":
        return jax.random.randint(jax.random.PRNGKey(1), (R,), 0, T)
    piled = [5] * TOP_K + [4, 6, 7, 8] * 4
    return jnp.asarray(piled[:R], jnp.int32)


def _held(count):
    return jnp.arange(R) < count


COUNTS = pytest.mark.parametrize("count", [0, 1, 7, R],
                                 ids=["none", "one", "ragged", "all"])
DTYPES = pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                                 ids=["f32", "bf16"])
TOKENS = pytest.mark.parametrize("kind", ["spread", "piled"])


@COUNTS
@DTYPES
@TOKENS
def test_gather_is_take_up_to_the_count_and_zero_past_it(count, dtype, kind):
    src = jax.random.normal(jax.random.PRNGKey(0), (T, D), dtype)
    token = _tokens(kind)
    got = moe_rows.moe_rows_gather(src, token, count)
    want = jnp.where(_held(count)[:, None], jnp.take(src, token, axis=0), 0)
    assert got.dtype == dtype and got.shape == (R, D)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@COUNTS
@DTYPES
@TOKENS
@pytest.mark.parametrize("weighted", [True, False], ids=["weights", "ones"])
def test_combine_is_the_float32_scatter_add_of_the_held_rows(count, dtype,
                                                             kind, weighted):
    rows = jax.random.normal(jax.random.PRNGKey(2), (R, D), dtype)
    weight = jax.random.uniform(jax.random.PRNGKey(3), (R,)) if weighted else None
    token = _tokens(kind)
    got = moe_rows.moe_rows_combine(rows, weight, token, count, T)
    scale = jnp.where(_held(count), 1.0 if weight is None else weight, 0.0)
    want = jnp.zeros((T, D), jnp.float32).at[token].add(
        scale[:, None] * rows.astype(jnp.float32))
    assert got.dtype == jnp.float32 and got.shape == (T, D)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    if kind == "piled" and count == R:
        assert not np.asarray(got[:4]).any() and not np.asarray(got[9:]).any()


@pytest.mark.parametrize("d,slab", [(2688, 24), (1280, 16), (1024, 8), (200, 1)],
                         ids=["2688-21-slabs", "1280-10-slabs", "1024", "200"])
@DTYPES
def test_a_row_that_is_no_whole_number_of_tiles_moves_padded(d, slab, dtype):
    """2,688 = 21 x 128: a row's slab is padded to 24 rows of 128 lanes (a
    DMA takes whole tiles of 8) and the kernels write the 2,688 real columns;
    a width that is no multiple of 128 stays one row of its own."""
    assert moe_rows._slabs(R, d)[1] == slab
    rows = jax.random.normal(jax.random.PRNGKey(2), (R, d), dtype)
    weight = jax.random.uniform(jax.random.PRNGKey(3), (R,))
    token = _tokens("spread")
    got = moe_rows.moe_rows_combine(rows, weight, token, 11, T)
    scale = jnp.where(_held(11), weight, 0.0)
    want = jnp.zeros((T, d), jnp.float32).at[token].add(
        scale[:, None] * rows.astype(jnp.float32))
    assert got.shape == (T, d)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    src = jax.random.normal(jax.random.PRNGKey(0), (T, d), dtype)
    taken = moe_rows.moe_rows_gather(src, token, 11)
    assert taken.shape == (R, d) and taken.dtype == dtype
    np.testing.assert_array_equal(
        np.asarray(taken, np.float32),
        np.asarray(jnp.where(_held(11)[:, None], jnp.take(src, token, axis=0), 0),
                   np.float32))


def test_a_tokens_rows_are_added_in_row_order():
    """Deterministic where the scatter-add was not: ((a + b) + c) + d in
    float32 for the four rows of token 5, whatever else the tile holds."""
    rows = jnp.asarray([[1e8], [1.0], [-1e8], [1.0]] + [[0.5]] * (R - 4),
                       jnp.float32) * jnp.ones((1, D))
    got = moe_rows.moe_rows_combine(rows, None, _tokens("piled"), R, T)
    in_order = np.float32(np.float32(np.float32(1e8) + np.float32(1.0))
                          + np.float32(-1e8)) + np.float32(1.0)
    assert float(got[5, 0]) == float(in_order) == 1.0


def test_the_plan_lists_held_rows_by_token_and_each_tiles_first_entry():
    token = _tokens("spread")
    plan = moe_rows.combine_plan(token, 7, T)
    order, starts = np.asarray(plan.order), np.asarray(plan.starts)
    held = np.asarray(token[:7])
    assert sorted(order[:7]) == list(range(7))
    assert list(held[order[:7]]) == sorted(held)
    assert list(starts) == [int((held < b).sum()) for b in (0, 8, 16, T)]


# --------------------------------------- the share's pass on the two kernels

def _xla_take_rows(x, token, n_tokens):
    """The dispatch as it stood before the kernels: XLA's gather, with the
    transpose that adds a token's rows up in float32."""
    @jax.custom_vjp
    def take(x):
        return jnp.take(x, token, axis=0)

    def bwd(_, g):
        dx = jnp.zeros((n_tokens, g.shape[-1]), jnp.float32).at[token].add(
            g.astype(jnp.float32))
        return (dx.astype(g.dtype),)

    take.defvjp(lambda x: (take(x), None), bwd)
    return take(x)


def _xla_held_pass(c, x, weights, gate, up, down, perm, offsets, top_k, bound):
    """``moe._held_pass`` as PR 32 left it: four XLA row operations."""
    n_tokens, d = x.shape
    first = c * bound
    kept = jax.lax.dynamic_slice(perm, (first,), (bound,))
    token = kept // top_k
    sizes = jnp.diff(jnp.clip(offsets, first, first + bound))
    weight = jnp.where(first + jnp.arange(bound) < offsets[-1],
                       jnp.take(weights, kept), 0.0)
    rows = _xla_take_rows(x, token, n_tokens)
    out = moe._expert_mlps(rows, gate, up, down, sizes)
    return jnp.zeros((n_tokens, d), jnp.float32).at[token].add(
        weight[:, None] * out.astype(jnp.float32))


def _xla_held_passes(x, weights, gate, up, down, perm, offsets, top_k, bound,
                     passes):
    """Every pass, unrolled (the test knows how many the routing takes)."""
    return sum(_xla_held_pass(c, x, weights, gate, up, down, perm, offsets,
                              top_k, bound) for c in range(passes))


def _routing(tokens=24, width=8, held=2, first=4, k=2, d=128, w=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (tokens, d))
    scores = jax.nn.sigmoid(jax.random.normal(keys[1], (tokens, width)))
    # everyone's first choice is held, the second one in three tokens'
    bias = jnp.zeros(width).at[first].set(10.0)
    r = moe.sigmoid_topk_route(scores, k, bias, first_expert=first, n_held=held)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(r.group_sizes, dtype=jnp.int32)])
    bank = [0.1 * jax.random.normal(key, shape) for key, shape in zip(
        keys[2:5], ((held, d, w), (held, d, w), (held, w, d)))]
    return x, r, offsets, bank, int(offsets[-1])


def _padded(perm, bound):
    """As ``routed_experts`` hands it to the passes: whole passes long."""
    return jnp.pad(perm, (0, -(-perm.size // bound) * bound - perm.size))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("c", [0, 1], ids=["pass-0", "pass-1"])
def test_a_pass_on_the_kernels_is_the_pass_on_xlas_operations(c, dtype, tol):
    """Value and the gradients of x, the weights and the three banks of one
    pass, the first (full) and the second (ragged: the held rows end inside
    it), against the XLA formulation."""
    x, r, offsets, bank, held_rows = _routing()
    bound, k = 20, 2
    assert bound < held_rows < 2 * bound
    perm = _padded(r.perm, bound)
    x = x.astype(dtype)
    target = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def loss(fn):
        return lambda x, weights, *bank: (fn(
            c, x, weights, *bank, perm, offsets, top_k=k, bound=bound)
            * target).sum()
    args = (x, r.weights.reshape(-1), *bank)
    np.testing.assert_allclose(
        moe._held_pass(c, *args, perm, offsets, k, bound),
        _xla_held_pass(c, *args, perm, offsets, k, bound), rtol=tol, atol=tol)
    got = jax.jit(jax.grad(loss(moe._held_pass), argnums=range(5)))(*args)
    want = jax.jit(jax.grad(loss(_xla_held_pass), argnums=range(5)))(*args)
    for g, w, name in zip(got, want, ("x", "weights", "gate", "up", "down")):
        assert g.dtype == w.dtype, name
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=tol, atol=tol, err_msg=name)


@pytest.mark.parametrize("bound,passes", [(20, 2), (12, 3)],
                         ids=["2-passes", "3-passes"])
def test_the_passes_on_the_kernels_are_the_passes_on_xlas_operations(bound,
                                                                     passes):
    """``_held_passes`` under a bound below the held rows: pass 0 kept, the
    others recomputed, each on the two kernels; value and the five
    gradients against the unrolled XLA passes."""
    x, r, offsets, bank, held_rows = _routing()
    k = 2
    assert -(-held_rows // bound) == passes
    perm = _padded(r.perm, bound)
    target = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    args = (x, r.weights.reshape(-1), *bank)
    ours = lambda *a: moe._held_passes(*a, perm, offsets, k, bound)  # noqa: E731
    xla = lambda *a: _xla_held_passes(*a, perm, offsets, k, bound, passes)  # noqa: E731
    np.testing.assert_allclose(ours(*args), xla(*args), rtol=1e-5, atol=1e-5)
    got = jax.jit(jax.grad(lambda *a: (ours(*a) * target).sum(),
                           argnums=range(5)))(*args)
    want = jax.jit(jax.grad(lambda *a: (xla(*a) * target).sum(),
                            argnums=range(5)))(*args)
    for g, w, name in zip(got, want, ("x", "weights", "gate", "up", "down")):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5, err_msg=name)


def test_the_share_says_how_many_row_operations_a_kernel_carries():
    """``moe.rows.by_kernel``: the two sums of a token's rows of a share's
    pass (its two gathers are XLA's), none of the whole bank's four (a
    permutation has no rows to add up)."""
    x, r, offsets, bank, _ = _routing()
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(1), (24, 8)))
    share = functools.partial(moe.routed_experts, top_k=2,
                              route=moe.sigmoid_topk_route)
    jax.make_jaxpr(lambda x: share(x, scores, *bank, first_expert=4,
                                   rows_bound=20)[0])(x)
    assert telemetry.gauge("moe.rows.by_kernel").value == 2
    whole = [jnp.concatenate([b] * 4) for b in bank]
    jax.make_jaxpr(lambda x: share(x, scores, *whole)[0])(x)
    assert telemetry.gauge("moe.rows.by_kernel").value == 0


def test_the_row_move_reader_sums_xlas_group_and_the_kernels_over_steps_and_chips():
    """``benchmark/layers/moe_row_move_ms_per_step.py``: a parent's trace
    holds time under ``fusion (kCustom)`` alone, the change's under the
    kernel's name too; nothing to read without a trace."""
    import importlib.util
    import os
    import sys
    import types
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        spec = importlib.util.spec_from_file_location(
            "moe_row_move_ms_per_step", os.path.join(
                root, "benchmark", "layers", "moe_row_move_ms_per_step.py"))
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
    finally:
        sys.path.remove(root)

    def trace(*groups):
        return types.SimpleNamespace(devices={
            i: types.SimpleNamespace(by_group=g) for i, g in enumerate(groups)})
    parent = trace({"fusion (kCustom)": 0.4, "fusion (kLoop)": 9.0})
    change = trace({"fusion (kCustom)": 0.2, "pallas:moe_rows_combine": 0.06,
                    "pallas:moe_gmm_fwd": 1.0},
                   {"fusion (kCustom)": 0.2, "pallas:moe_rows_gather": 0.02})
    assert reader.read({"trace": parent, "trace_steps": 16}) == pytest.approx(25.0)
    assert reader.read({"trace": change, "trace_steps": 16}) == pytest.approx(15.0)
    assert reader.read({"trace": None, "trace_steps": 16}) is None
