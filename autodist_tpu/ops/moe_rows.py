"""Row movement by index — two pallas TPU kernels, each other's transpose.

One chip's share of a layer's experts (``models/moe.py`` ``_held_pass``)
moves ``R`` compacted rows of width ``d`` between the ``T`` tokens and the
grouped matmuls, of which only the first ``count`` are held: the rest of the
buffer names rows of absent experts. XLA's scatter-add, with a token's rows
as repeated indices, takes 115 ns a row on a v5e, held or not. Here the
indices and the count are scalar-prefetch arguments, a row moves HBM -> VMEM
by one DMA, and no DMA is issued for a row that is not held:

- ``moe_rows_combine``: ``y[t] = sum of weight[r] * rows[r]`` over the held
  rows ``r`` of token ``t``, in float32, in row order. Grid over token tiles;
  a tile fetches its own held rows (:func:`combine_plan`: the held rows
  sorted by token and each tile's first entry) into a VMEM buffer, adds them
  up there and writes its ``[tokens, d]`` block once, in the dtype asked for.
  A token with no held row costs no copy. The share's combine (weighted) and
  its dispatch's transpose (not) run on it: 46 ns a held row.
- ``moe_rows_gather``:  ``out[i] = src[token[i]]`` for ``i < count``, zero
  past it. Grid over row tiles; a tile's copies land in its output block,
  all started before the first is waited for. **On no step's path**: XLA's
  own gather moves a 4 KB row in 6 ns and an 8 KB row in 19-41 ns on this
  chip, rows not held included, where one DMA a held row takes 21-48 ns
  before the two layout copies the 3-D view costs (PERF.md §6, PR 34). It
  stays as the combine's measured transpose: ``tools/moe_timing.py --phases
  rows`` times both against XLA's operations, and a Mosaic that takes a
  one-row slice of a 2-D ref would change the reading.

A row is one DMA because the kernels see ``[n, d]`` in HBM as ``[n, d / 128,
128]``: a row taken by its leading index is a whole slab of tiles, where
Mosaic refuses a one-row slice of a 2-D HBM ref (the tiling holds 8 or 16
rows). XLA pays for the view with a layout copy of the operand; where ``d /
128`` is not a multiple of 8 (2,688 = 21 x 128) that copy also pads the row
to whole tiles of 8 slab rows, which a DMA's slice must be, and the kernels
write the ``d`` real columns. On the CPU
backend the kernels run in pallas interpret mode; ``tests/test_chip_compile.py``
compiles them for a described v5e at the two cells' shapes.
"""

import functools
import importlib
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.ops.named_call import named_pallas_call

# The module, not the function ``autodist_tpu.ops`` exports under the same
# name: ``_use_interpret`` is looked up in it at call time (see grouped_matmul).
_flash = importlib.import_module("autodist_tpu.ops.flash_attention")

ROW_TILE = 256          # rows an output block of the gather holds
TOKEN_TILE = 256        # tokens an output block of the combine holds
FETCH_ROWS = 128        # rows the combine fetches before it adds them up
_LANES = 128


class RowPlan(NamedTuple):
    """How the combine walks the held rows: by token."""
    order: jax.Array     # [R] entry k is row order[k]; held rows first, by token
    starts: jax.Array    # [token tiles + 1] first entry of each tile, then count


_SLAB_ROWS = 8          # Mosaic's tile: a DMA takes whole tiles of a slab


def _slabs(n: int, d: int):
    """The 3-D shape a ``[n, d]`` operand takes: a row is one slab, of whole
    tiles."""
    if d % _LANES:
        return (n, 1, d)
    return (n, -(-d // (_LANES * _SLAB_ROWS)) * _SLAB_ROWS, _LANES)


def _as_slabs(x):
    """``x [n, d]`` in its 3-D view, zero columns added up to whole tiles."""
    view = _slabs(*x.shape)
    pad = view[1] * view[2] - x.shape[1]
    return (jnp.pad(x, ((0, 0), (0, pad))) if pad else x).reshape(view)


def _token_tile(n_tokens: int) -> int:
    return min(TOKEN_TILE, n_tokens)


def combine_plan(token, count, n_tokens: int) -> RowPlan:
    """The held rows ``[0, count)`` in token order (a token's rows in row
    order: the sort is stable) and, for each tile of :data:`TOKEN_TILE`
    tokens, where its rows begin in that order."""
    n_rows = token.shape[0]
    rows = jnp.arange(n_rows, dtype=jnp.int32)
    key = jnp.where(rows < count, token.astype(jnp.int32), n_tokens)
    key, order = jax.lax.sort((key, rows), num_keys=1, is_stable=True)
    tt = _token_tile(n_tokens)
    bounds = jnp.minimum(jnp.arange(pl.cdiv(n_tokens, tt) + 1) * tt, n_tokens)
    starts = jnp.sum(key[None, :] < bounds[:, None], axis=1, dtype=jnp.int32)
    return RowPlan(order, starts)


# ------------------------------------------------------------------ gather

def _gather_kernel(token, count, src, out, sem, *, tr: int):
    i = pl.program_id(0)
    n = jnp.clip(count[0] - i * tr, 0, tr)      # held rows of this tile

    @pl.when(n > 0)
    def _fetch():
        def start(r, _):
            pltpu.make_async_copy(src.at[token[i * tr + r]], out.at[r],
                                  sem).start()

        def wait(r, _):
            pltpu.make_async_copy(src.at[0], out.at[0], sem).wait()

        jax.lax.fori_loop(0, n, start, None)
        jax.lax.fori_loop(0, n, wait, None)

    @pl.when(n == 0)
    def _empty():
        out[...] = jnp.zeros_like(out)

    @pl.when((n > 0) & (n < tr))
    def _ragged():
        # after the waits: what the copies did not write is undefined
        held = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0) < n
        out[...] = jnp.where(held, out[...], jnp.zeros_like(out))


def moe_rows_gather(src: jax.Array, token: jax.Array, count) -> jax.Array:
    """``out[i] = src[token[i]]`` for ``i < count``, zero for ``i >= count``.

    src: ``[T, d]``; token: int ``[R]`` (entries past ``count`` are not
    read); count: int scalar, ``0 <= count <= R``. Returns ``[R, d]`` in
    ``src.dtype``."""
    n_tokens, d = src.shape
    n_rows = token.shape[0]
    tr = min(ROW_TILE, n_rows)
    view = _slabs(n_tokens, d)
    out = named_pallas_call(
        "moe_rows_gather", functools.partial(_gather_kernel, tr=tr),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(pl.cdiv(n_rows, tr),),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tr,) + view[1:],
                                   lambda i, token, count: (i, 0, 0)),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct(_slabs(n_rows, d), src.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_flash._use_interpret(),
    )(token.astype(jnp.int32), jnp.asarray(count, jnp.int32).reshape(1),
      _as_slabs(src))
    return out.reshape(n_rows, -1)[:, :d]


# ----------------------------------------------------------------- combine

def _combine_kernel(order, starts, token, *refs, tt: int, fetch: int,
                    weighted: bool):
    if weighted:
        weight, rows, out, acc, buf, sem = refs
    else:
        rows, out, acc, buf, sem = refs
    i = pl.program_id(0)
    first, end = starts[i], starts[i + 1]
    slab = buf.shape[1]              # sublanes a row takes in ``acc``
    acc[...] = jnp.zeros_like(acc)

    def one_fetch(j, _):
        base = first + j * fetch
        n = jnp.minimum(fetch, end - base)

        def start(q, _):
            pltpu.make_async_copy(rows.at[order[base + q]], buf.at[q],
                                  sem).start()

        def wait(q, _):
            pltpu.make_async_copy(rows.at[0], buf.at[0], sem).wait()

        def add(q, _):
            row = order[base + q]
            at = pl.ds(pl.multiple_of((token[row] - i * tt) * slab, slab), slab)
            value = buf[q].astype(jnp.float32)
            if weighted:
                value = value * weight[row]
            acc[at, :] = acc[at, :] + value

        jax.lax.fori_loop(0, n, start, None)
        jax.lax.fori_loop(0, n, wait, None)
        jax.lax.fori_loop(0, n, add, None)

    jax.lax.fori_loop(0, pl.cdiv(end - first, fetch), one_fetch, None)
    # token t's slab is rows [t * slab, (t + 1) * slab) of ``acc``; the
    # output block is [tokens, d]: column block j is every slab's row j
    lanes = acc.shape[1]
    for j in range(pl.cdiv(out.shape[1], lanes)):   # the real columns' slabs
        out[:, j * lanes:(j + 1) * lanes] = acc[
            pl.ds(j, out.shape[0], stride=slab), :].astype(out.dtype)


def moe_rows_combine(rows: jax.Array, weight: Optional[jax.Array],
                     token: jax.Array, count, n_tokens: int,
                     plan: Optional[RowPlan] = None,
                     dtype=jnp.float32) -> jax.Array:
    """``y[t] = sum over r < count with token[r] == t of weight[r] * rows[r]``
    in float32, a token's rows added in row order; a token with no held row
    is zero. The transpose of :func:`moe_rows_gather`, weighted.

    rows: ``[R, d]``; weight: float32 ``[R]`` or None (ones); token: int
    ``[R]``; count: int scalar; plan: :func:`combine_plan` of the same
    ``token``, ``count`` and ``n_tokens`` where the caller already has it.
    Returns ``[n_tokens, d]`` in ``dtype`` (the float32 sums, rounded once)."""
    n_rows, d = rows.shape
    plan = combine_plan(token, count, n_tokens) if plan is None else plan
    tt = _token_tile(n_tokens)
    fetch = min(FETCH_ROWS, n_rows)
    _, slab, lanes = view = _slabs(n_rows, d)
    weighted = weight is not None
    scalars = (plan.order, plan.starts, token.astype(jnp.int32))
    if weighted:
        scalars += (weight.astype(jnp.float32),)
    return named_pallas_call(
        "moe_rows_combine",
        functools.partial(_combine_kernel, tt=tt, fetch=fetch,
                          weighted=weighted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(pl.cdiv(n_tokens, tt),),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tt, d), lambda i, *scalars: (i, 0)),
            scratch_shapes=[pltpu.VMEM((tt * slab, lanes), jnp.float32),
                            pltpu.VMEM((fetch, slab, lanes), rows.dtype),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((n_tokens, d), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_flash._use_interpret(),
    )(*scalars, _as_slabs(rows))
