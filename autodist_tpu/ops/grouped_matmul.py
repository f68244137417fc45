"""Grouped matmul — pallas TPU kernels, forward, dX and dW.

``gmm(x [R, k], w [E, k, n], group_sizes [E]) -> [R, n]``: the rows of ``x``
are sorted by group (an MoE layer's token x slot rows sorted by expert), group
``e`` owns the next ``group_sizes[e]`` rows and is multiplied by ``w[e]``.
Groups are ragged and known only at run time; an empty group is legal, and so
are rows past the last group (``sum(group_sizes) < R``: rows routed to experts
another device holds), which come back zero and take no gradient.

The design is the public megablox one. Rows are cut into tiles of ``tm``; a
list of *visits* is computed on the device from ``group_sizes`` and handed to
the kernel as scalar-prefetch arguments, so the index maps read from it which
row tile and which group's weights a grid step needs before the step runs. A
tile that straddles two groups is visited once for each, under a row mask;
the output block stays in VMEM between two consecutive visits of one row tile,
so the second visit fills in its rows beside the first's. The grid is static
(``tiles + E`` visits at most); visits past the last real one name the same
blocks as the last real one and do nothing, so nothing is copied for them.

Three kernels, bf16 operands (``w`` is cast to ``x.dtype`` once, outside),
float32 accumulation:

- ``moe_gmm_fwd``:    ``y = x . w[g]``, grid (n tiles, visits), the
  contraction whole. A group's weight block stays resident across its row
  tiles: it is copied once a group and n tile, not once a row tile.
- ``moe_gmm_bwd_dx``: ``dx = dy . w[g]^T``: the same kernel contracting the
  bank's last dimension (the MXU's native transposed-operand product).
- ``moe_gmm_bwd_dw``: ``dw[g] = x_g^T . dy_g`` in float32, grid
  (k tiles, n tiles, visits): the output block of a group is the accumulator
  across the group's row tiles; an empty group is visited once to write zeros.

Each visit runs the body of its class: *plain* (the tile lies wholly inside
its group: no iota, no compare, no select) or *masked* (a straddled tile, the
ragged last tile, the tail). On the CPU backend the kernels run in pallas
interpret mode; ``tests/test_chip_compile.py`` compiles them for a described
v5e at the OLMoE cell's shapes.
"""

import functools
import importlib
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu import telemetry
from autodist_tpu.ops.named_call import named_pallas_call

# The module, not the function ``autodist_tpu.ops`` exports under the same name:
# ``_use_interpret`` is looked up in it at call time, so a compile rehearsal that
# steers the flash kernels to compile steers these too.
_flash = importlib.import_module("autodist_tpu.ops.flash_attention")

# Tiles, from stand-alone timings on a TPU v5e (tools/moe_timing.py, PERF.md §6
# "PR 25") at the OLMoE cell's shapes: 131,072 rows in 64 groups, 2,048 x 1,024.
ROW_TILE = 256          # rows a visit, forward and dX
DW_ROW_TILE = 512       # rows a visit of the dW kernel (its contraction)
# Scoped VMEM the blocks may take, and what the kernels ask Mosaic for (its
# default limit on v5e is 16 MiB of the chip's 128).
_VMEM_BUDGET = 40 << 20
_VMEM_LIMIT = _VMEM_BUDGET + (8 << 20)


class _Visits(NamedTuple):
    """The scalar-prefetch arguments: which (row tile, group) each grid step
    works on. Where the tail ``[sum(group_sizes), R)`` is visited it is one
    more group, after the last real one."""
    offsets: jax.Array      # [groups + 1] first row of each group, then the end
    group_ids: jax.Array    # [V] group of visit v (G: the tail)
    row_tiles: jax.Array    # [V] row tile of visit v
    count: jax.Array        # [1] real visits; the rest repeat the last one


def _col_tile(n: int) -> int:
    """Output columns a block: the widest of 1,024/512/256 that divides
    ``n``; failing those the widest multiple of 128 up to 1,024 that does
    (2,688 = 3 x 896, where 128 alone would make 21 blocks); or ``n`` itself,
    one block, where ``n`` is no multiple of 128 (1,856 = 14.5 x 128)."""
    for t in (1024, 512, 256):
        if n % t == 0:
            return t
    if n % 128:
        return n
    return max(t for t in range(128, 1025, 128) if n % t == 0)


def _plan_visits(group_sizes, rows: int, tm: int, *, tail: bool,
                 visit_empty: bool) -> _Visits:
    """Visits in group order, a group's row tiles in row order. ``tail``: the
    rows past the last group are visited too (the forward zeroes them).
    ``visit_empty``: an empty group gets one visit (dW writes its zeros)."""
    n_groups = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    if tail:
        ends = jnp.concatenate([ends, jnp.full((1,), rows, jnp.int32)])
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    offsets = jnp.concatenate([starts, ends[-1:]])
    n_tiles = pl.cdiv(rows, tm)
    first = jnp.minimum(starts // tm, n_tiles - 1)
    tiles = jnp.where(ends > starts, (ends - 1) // tm - starts // tm + 1,
                      1 if visit_empty else 0)
    visit_end = jnp.cumsum(tiles)
    count = visit_end[-1]
    # Every boundary between two groups can add a visit to the row tiles.
    n_visits = n_tiles + n_groups * (2 if visit_empty else 1)
    v = jnp.minimum(jnp.arange(n_visits, dtype=jnp.int32),
                    jnp.maximum(count - 1, 0))
    gid = jnp.searchsorted(visit_end, v, side="right").astype(jnp.int32)
    gid = jnp.minimum(gid, tiles.shape[0] - 1)
    row_tile = first[gid] + v - (visit_end[gid] - tiles[gid])
    return _Visits(offsets, gid, jnp.minimum(row_tile, n_tiles - 1),
                   count.reshape(1))


def _visit_facts(visits_refs, v, tm: int):
    """(group, first row of the tile, group's row range, first visit of this
    row tile, real visit) of grid step ``v``, from the SMEM scalars."""
    offsets, group_ids, row_tiles, count = visits_refs
    g = group_ids[v]
    tile = row_tiles[v]
    first_visit = jnp.logical_or(v == 0,
                                 row_tiles[jnp.maximum(v - 1, 0)] != tile)
    return g, tile * tm, offsets[g], offsets[g + 1], first_visit, v < count[0]


def _row_mask(row0, start, end, shape):
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return jnp.logical_and(rows >= start, rows < end)


# ------------------------------------------------------- forward and dX

def _gmm_kernel(offsets, group_ids, row_tiles, count, x_ref, w_ref, o_ref, *,
                tm: int, n_groups: int, transpose_w: bool):
    v = pl.program_id(1)
    g, row0, start, end, first_visit, real = _visit_facts(
        (offsets, group_ids, row_tiles, count), v, tm)
    is_tail = g == n_groups
    inside = jnp.logical_and(start <= row0, row0 + tm <= end)
    dims = (((1,), (1,)), ((), ())) if transpose_w else (((1,), (0,)), ((), ()))

    def product():
        return jax.lax.dot_general(x_ref[...], w_ref[0], dims,
                                   preferred_element_type=jnp.float32)

    def kept():
        # What the rows outside this visit's group keep: what an earlier
        # visit of the same row tile wrote (the block is still in VMEM), zero
        # on the tile's first visit (the block holds nothing yet).
        return jnp.where(first_visit, jnp.zeros_like(o_ref), o_ref[...])

    @pl.when(real & ~is_tail & inside)
    def _plain():
        o_ref[...] = product().astype(o_ref.dtype)

    @pl.when(real & ~is_tail & ~inside)
    def _masked():
        mask = _row_mask(row0, start, end, o_ref.shape)
        o_ref[...] = jnp.where(mask, product().astype(o_ref.dtype), kept())

    @pl.when(real & is_tail)
    def _tail():
        mask = _row_mask(row0, start, end, o_ref.shape)
        o_ref[...] = jnp.where(mask, jnp.zeros_like(o_ref), kept())


def _gmm_call(name: str, x, w, group_sizes, transpose_w: bool, interpret: bool):
    """``x [R, c] . w[g] -> [R, n]``; ``w`` is ``[G, c, n]``, or ``[G, n, c]``
    under ``transpose_w``."""
    rows, c = x.shape
    n_groups = w.shape[0]
    n = w.shape[1] if transpose_w else w.shape[2]
    tm = min(ROW_TILE, rows)
    size = x.dtype.itemsize

    def need(tn_):      # double-buffered x, w and out blocks, the f32 product
        return (2 * tm * c + 2 * c * tn_ + 2 * tm * tn_) * size + 4 * tm * tn_
    tn = _col_tile(n)
    while need(tn) > _VMEM_BUDGET and tn % 256 == 0:
        tn //= 2
    if need(tn) > _VMEM_BUDGET:
        raise ValueError(
            f"gmm: a [{tm}, {c}] row tile against a [{c}, {tn}] weight block "
            f"needs {need(tn) / 2**20:.1f} MiB of VMEM (budget "
            f"{_VMEM_BUDGET / 2**20:.0f} MiB); the contraction is not tiled")
    visits = _plan_visits(group_sizes, rows, tm, tail=True, visit_empty=False)
    telemetry.gauge("moe.gmm.row_tiles").set(int(visits.group_ids.shape[0]))
    last = n_groups - 1
    if transpose_w:
        w_spec = pl.BlockSpec(
            (1, tn, c), lambda j, v, offs, gids, tiles, cnt:
            (jnp.minimum(gids[v], last), j, 0))
    else:
        w_spec = pl.BlockSpec(
            (1, c, tn), lambda j, v, offs, gids, tiles, cnt:
            (jnp.minimum(gids[v], last), 0, j))
    return named_pallas_call(
        name,
        functools.partial(_gmm_kernel, tm=tm, n_groups=n_groups,
                          transpose_w=transpose_w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, visits.group_ids.shape[0]),
            in_specs=[
                pl.BlockSpec((tm, c), lambda j, v, offs, gids, tiles, cnt:
                             (tiles[v], 0)),
                w_spec,
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, v, offs, gids, tiles,
                                   cnt: (tiles[v], j))),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*visits, x, w)


# ------------------------------------------------------------------- dW

def _dw_kernel(offsets, group_ids, row_tiles, count, x_ref, dy_ref, dw_ref, *,
               tm: int):
    v = pl.program_id(2)
    g, row0, start, end, _, real = _visit_facts(
        (offsets, group_ids, row_tiles, count), v, tm)
    first_of_group = jnp.logical_or(v == 0,
                                    group_ids[jnp.maximum(v - 1, 0)] != g)
    inside = jnp.logical_and(start <= row0, row0 + tm <= end)

    def product(x, dy):
        return jax.lax.dot_general(x, dy, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def accumulate(update):
        # The output block of a group is its accumulator: it stays in VMEM
        # until the next group's block takes its place.
        @pl.when(first_of_group)
        def _set():
            dw_ref[0] = update

        @pl.when(~first_of_group)
        def _add():
            dw_ref[0] += update

    @pl.when(real & inside)
    def _plain():
        accumulate(product(x_ref[...], dy_ref[...]))

    @pl.when(real & ~inside)
    def _masked():
        # The contraction runs over the rows, so a row of another group (or
        # the undefined rows of a ragged last tile) must be a hard zero on
        # both operands: 0 x inf would be NaN.
        x = jnp.where(_row_mask(row0, start, end, x_ref.shape), x_ref[...],
                      jnp.zeros_like(x_ref))
        dy = jnp.where(_row_mask(row0, start, end, dy_ref.shape), dy_ref[...],
                       jnp.zeros_like(dy_ref))
        accumulate(product(x, dy))


def _dw_call(x, dy, group_sizes, n_groups: int, interpret: bool):
    """``dw[g] = x_g^T . dy_g``: float32 ``[G, c, n]`` from ``x [R, c]`` and
    ``dy [R, n]`` (the output block is the accumulator, so it is float32
    whatever the bank's dtype)."""
    rows, c = x.shape
    n = dy.shape[1]
    tm = min(DW_ROW_TILE, rows)
    size = x.dtype.itemsize

    def need(tc_, tn_):
        return (2 * tm * (tc_ + tn_) * size + 2 * tc_ * tn_ * 4
                + 4 * tc_ * tn_)
    tc, tn = _col_tile(c), _col_tile(n)
    while need(tc, tn) > _VMEM_BUDGET and max(tc, tn) % 256 == 0:
        if tc >= tn:
            tc //= 2
        else:
            tn //= 2
    visits = _plan_visits(group_sizes, rows, tm, tail=False, visit_empty=True)
    return named_pallas_call(
        "moe_gmm_bwd_dw", functools.partial(_dw_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(c // tc, n // tn, visits.group_ids.shape[0]),
            in_specs=[
                pl.BlockSpec((tm, tc), lambda i, j, v, offs, gids, tiles, cnt:
                             (tiles[v], i)),
                pl.BlockSpec((tm, tn), lambda i, j, v, offs, gids, tiles, cnt:
                             (tiles[v], j)),
            ],
            out_specs=pl.BlockSpec(
                (1, tc, tn), lambda i, j, v, offs, gids, tiles, cnt:
                (gids[v], i, j))),
        out_shape=jax.ShapeDtypeStruct((n_groups, c, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*visits, x, dy)


# ------------------------------------------------------------- public op

@jax.custom_vjp
def _gmm(x, w, group_sizes):
    return _gmm_fwd(x, w, group_sizes)[0]


def _gmm_fwd(x, w, group_sizes):
    y = _gmm_call("moe_gmm_fwd", x, w.astype(x.dtype), group_sizes, False,
                  _flash._use_interpret())
    return y, (x, w, group_sizes)


def _gmm_bwd(residuals, dy):
    # The bank is saved as the caller holds it (float32 parameters) and cast
    # again here: a bfloat16 copy kept from the forward would be a third of
    # the bank's bytes held through the whole backward pass.
    x, w, group_sizes = residuals
    interpret = _flash._use_interpret()
    dy = dy.astype(x.dtype)
    dx = _gmm_call("moe_gmm_bwd_dx", dy, w.astype(x.dtype), group_sizes, True,
                   interpret)
    dw = _dw_call(x, dy, group_sizes, w.shape[0], interpret)
    return dx, dw.astype(w.dtype), None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def gmm(x: jax.Array, w: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """``y[r] = x[r] . w[group of r]`` for rows sorted by group.

    x: ``[R, k]`` (bf16 or f32); w: ``[E, k, n]`` in any float dtype (cast to
    ``x.dtype`` for the products; its gradient comes back in its own dtype);
    group_sizes: int ``[E]``, ``sum <= R``. Returns ``[R, n]`` in ``x.dtype``;
    rows past the last group are zero. Differentiable in ``x`` and ``w``.

    Under a mesh of several devices the kernels run per device
    (:func:`autodist_tpu.parallel.mesh.per_device`) on the whole of their
    arguments: the caller hands each device its own rows and bank.
    """
    from autodist_tpu.parallel.mesh import per_device
    if x.ndim != 2 or w.ndim != 3 or x.shape[1] != w.shape[1]:
        raise ValueError(f"gmm: x {x.shape} against a bank {w.shape}")
    if group_sizes.shape != (w.shape[0],):
        raise ValueError(f"gmm: {w.shape[0]} groups, group_sizes "
                         f"{group_sizes.shape}")
    return per_device(_gmm, (x, w, group_sizes), batched=(False, False, False))
