"""The grouped matmul (``ops/grouped_matmul.py``) against a per-expert loop:
forward, dX and dW, in interpret mode on the CPU mesh, at row tiles small
enough that groups straddle them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.ops import grouped_matmul
from autodist_tpu.ops.grouped_matmul import gmm

K, N = 24, 40


def _loop(x, w, sizes):
    """Each group's rows against its own matrix; rows past the last group
    are zero."""
    outs, start = [], 0
    for e, size in enumerate(sizes):
        outs.append(x[start:start + size] @ w[e])
        start += size
    outs.append(jnp.zeros((x.shape[0] - start, w.shape[2]), x.dtype))
    return jnp.concatenate(outs)


@pytest.fixture
def small_tiles(monkeypatch):
    monkeypatch.setattr(grouped_matmul, "ROW_TILE", 16)
    monkeypatch.setattr(grouped_matmul, "DW_ROW_TILE", 16)


@pytest.mark.parametrize("sizes,rows", [
    ([16, 32, 16], 64),            # every group whole tiles: the plain body only
    ([5, 0, 20, 3, 9], 37),        # an empty group, groups smaller than a tile, ragged rows
    ([5, 0, 20, 3, 9], 50),        # ... and a tail no group owns
    ([0, 0, 64, 0], 64),           # empty groups first and last
    ([0, 3, 0, 0], 40),            # nearly everything is tail
    ([7, 9], 16),                  # one tile, two groups
    ([1] * 20, 20),                # more groups than tiles
], ids=["whole-tiles", "empty-small-ragged", "tail", "empty-edges",
        "mostly-tail", "one-tile", "many-groups"])
def test_gmm_and_its_gradients_match_a_per_expert_loop(small_tiles, sizes, rows):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(rows, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(len(sizes), K, N)), jnp.float32)
    ct = jnp.asarray(rng.normal(size=(rows, N)), jnp.float32)
    group_sizes = jnp.asarray(sizes, jnp.int32)

    got = gmm(x, w, group_sizes)
    np.testing.assert_allclose(got, _loop(x, w, sizes), rtol=1e-5, atol=1e-5)

    dx, dw = jax.jit(jax.grad(lambda x, w: jnp.sum(gmm(x, w, group_sizes) * ct),
                              argnums=(0, 1)))(x, w)
    rx, rw = jax.jit(jax.grad(lambda x, w: jnp.sum(_loop(x, w, sizes) * ct),
                              argnums=(0, 1)))(x, w)
    np.testing.assert_allclose(dx, rx, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dw, rw, rtol=1e-5, atol=1e-5)


def test_gmm_bf16_rows_against_a_float32_bank(small_tiles):
    """The cell's dtypes: bfloat16 rows, float32 bank cast per call, float32
    accumulation; the bank's gradient comes back float32."""
    rng = np.random.default_rng(1)
    sizes = [11, 0, 30, 7]
    x = jnp.asarray(rng.normal(size=(48, K)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(4, K, N)), jnp.float32)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    got = gmm(x, w, group_sizes)
    assert got.dtype == jnp.bfloat16
    want = _loop(x.astype(jnp.float32),
                 w.astype(jnp.bfloat16).astype(jnp.float32), sizes)
    # one bfloat16 rounding of the result (2^-8 relative) on values of a few units
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=1e-2,
                               atol=5e-2)
    dx, dw = jax.jit(jax.grad(lambda x, w: gmm(x, w, group_sizes)
                              .astype(jnp.float32).sum(), argnums=(0, 1)))(x, w)
    assert dx.dtype == jnp.bfloat16 and dw.dtype == jnp.float32
    assert float(jnp.abs(dw[1]).max()) == 0.0       # the empty group


def test_gmm_refuses_mismatched_arguments():
    x, w = jnp.zeros((8, 4)), jnp.zeros((2, 5, 3))
    with pytest.raises(ValueError, match="against a bank"):
        gmm(x, w, jnp.zeros((2,), jnp.int32))
    with pytest.raises(ValueError, match="group_sizes"):
        gmm(x, jnp.zeros((2, 4, 3)), jnp.zeros((3,), jnp.int32))


def test_row_tile_gauge_counts_the_grid_visits(small_tiles):
    """``moe.gmm.row_tiles``: the forward's grid, row tiles + one visit a
    group boundary can add (the tail is one more group)."""
    x, w = jnp.zeros((64, K)), jnp.zeros((5, K, N))
    jax.eval_shape(gmm, x, w, jnp.zeros((5,), jnp.int32))
    assert telemetry.gauge("moe.gmm.row_tiles").value == 64 // 16 + 5


@pytest.mark.parametrize("k,n", [(24, 200), (200, 24), (232, 336), (336, 232)],
                         ids=["n-200", "k-200", "up-232x336", "down-336x232"])
def test_gmm_at_widths_that_are_no_multiple_of_128(small_tiles, k, n):
    """Nemotron's expert is 1,856 = 14.5 x 128 wide under a hidden size of
    2,688 = 21 x 128: an output width that is no multiple of 128 is one block,
    a contraction that is none is whole; 232 and 336 are the same widths an
    eighth the size."""
    rng = np.random.default_rng(2)
    sizes = [9, 0, 21, 6]
    x = jnp.asarray(rng.normal(size=(40, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, k, n)), jnp.float32)
    ct = jnp.asarray(rng.normal(size=(40, n)), jnp.float32)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    np.testing.assert_allclose(gmm(x, w, group_sizes), _loop(x, w, sizes),
                               rtol=1e-4, atol=1e-4)
    got = jax.jit(jax.grad(lambda x, w: jnp.sum(gmm(x, w, group_sizes) * ct),
                           argnums=(0, 1)))(x, w)
    want = jax.jit(jax.grad(lambda x, w: jnp.sum(_loop(x, w, sizes) * ct),
                            argnums=(0, 1)))(x, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("width,tile", [
    (2048, 1024), (1024, 1024), (1536, 512),      # cells 6-8: the parent's tiles
    (512, 512), (256, 256), (128, 128),
    (2688, 896), (1856, 1856), (384, 384), (200, 200)])
def test_the_column_tile_of_a_width(width, tile):
    """The widths the older cells run keep the tiles they had; 2,688 = 3 x
    896 takes the widest multiple of 128 that divides it (the parent took 128:
    21 blocks), 1,856 = 14.5 x 128 is one block."""
    assert grouped_matmul._col_tile(width) == tile
    assert width % tile == 0


def test_gmm_at_the_nemotron_widths_in_real_tiles():
    """2,688 x 1,856 and back at the kernels' own tiles (256 and 512 rows, 896
    or 1,856 columns) in the cell's dtypes, a ragged share of a pass's rows;
    float32 rows at these widths do not fit the kernels' VMEM budget and are
    refused by name."""
    rng = np.random.default_rng(3)
    sizes = [300, 0, 130, 70]
    group_sizes = jnp.asarray(sizes, jnp.int32)
    rounded = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    for k, n in ((2688, 1856), (1856, 2688)):
        x = jnp.asarray(rng.normal(size=(640, k)) / np.sqrt(k), jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(4, k, n)), jnp.float32)
        ct = jnp.asarray(rng.normal(size=(640, n)), jnp.bfloat16)
        dense = lambda x, w: _loop(rounded(x), rounded(w), sizes)  # noqa: E731
        np.testing.assert_allclose(gmm(x, w, group_sizes).astype(jnp.float32),
                                   dense(x, w), rtol=2e-2, atol=2e-2)
        loss = lambda fn: lambda x, w: jnp.sum(  # noqa: E731
            fn(x, w).astype(jnp.float32) * ct.astype(jnp.float32))
        got = jax.jit(jax.grad(loss(lambda x, w: gmm(x, w, group_sizes)),
                               argnums=(0, 1)))(x, w)
        want = jax.jit(jax.grad(loss(dense), argnums=(0, 1)))(x, w)
        for g, r in zip(got, want):
            scale = float(jnp.abs(r.astype(jnp.float32)).max())
            np.testing.assert_allclose(g.astype(jnp.float32),
                                       r.astype(jnp.float32), rtol=2e-2,
                                       atol=2e-2 * scale)
    with pytest.raises(ValueError, match="MiB of VMEM"):
        gmm(jnp.zeros((640, 2688), jnp.float32),
            jnp.zeros((4, 2688, 1856), jnp.float32), group_sizes)
