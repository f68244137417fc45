"""Fused LM-head softmax cross-entropy — pallas TPU kernels.

The separable-head formulation of the LM loss is

    nll_n = lse_n - true_logit_n,   lse_n = logsumexp_v(h_n . w_v + b_v)

where the [N, V] logits tensor (4.2 GB at the flagship's N=65k, V=32k, bf16) is
pure intermediate: XLA materializes it out of the head matmul, reads it for the
log-softmax reductions, and reads/writes it again for d(logits) in the backward
— the single largest HBM consumer in the training step. These kernels compute
``lse`` (and its VJP) **without ever materializing logits in HBM**: each
[n-block, v-block] logits tile lives only in VMEM, reduced on the fly with the
same online-logsumexp state machine as the flash-attention kernel
(``ops/flash_attention.py``), and the backward recomputes tiles from the saved
``lse`` exactly like flash attention recomputes scores (FlashAttention-2 style).
The true-logit term is a cheap gather-einsum left to XLA.

``w`` is accepted in either layout — ``[D, V]`` (flax Dense kernel) or
``[V, H]`` (the reference's softmax_w; ``w_layout="vd"``) — and is cast to the
activation dtype **per tile inside the kernel**, so no transposed or downcast
copy of a multi-GiB table is ever materialized, and its gradient comes back in
the stored layout/dtype directly.

The kernels:
- forward: grid (n-blocks, v-blocks); VMEM scratch carries (m, l) across the v
  dimension; last v-block writes ``lse = m + log l``.
- backward, one pass (device name ``xent_bwd_dw``): grid (n-blocks,
  v-blocks); each step recomputes the logits tile and ``gp = exp(logits -
  lse) * g`` ONCE and makes from it dh's term ``gp @ w^T`` (accumulated in
  VMEM over a sweep of the vocabulary), the dw tile ``gp^T @ h`` and db's
  column sums. A float32 dw cannot stay resident across row blocks (412 MB
  at 2,048 x 50,304), so its tile accumulates through HBM: the dw output is
  aliased to an input, each step reads the tile the earlier row blocks left,
  adds and writes it back (the first row block writes without reading).
- backward, two kernels, for the shapes the one pass does not take
  (``_fit_blocks``): d(h), grid (n-blocks, v-blocks), accumulates
  ``gp @ w^T`` tiles in VMEM; d(w,b), grid (v-blocks, n-blocks), accumulates
  ``h^T @ gp`` and column sums. Both recompute the logits tile: five
  logits-sized products a call where the one pass runs the four the
  algorithm needs. All three share one block body (``_gp_tile``,
  ``_dh_product``, ``_dw_product``).

What a tile size decides is the kernel's arithmetic intensity as well as its
per-tile fixed cost, because each kernel holds a block of one operand and
streams the other past it from HBM once per block. Per byte streamed:
- forward: the table once per row block, one product a tile:
  2*bn*bv*d FLOP over w_size*d*bv bytes = ``2 * bn / w_size`` FLOP/byte;
- one-pass backward: per step the float32 w tile in, the dw tile in and the
  dw tile out, three products: 6*bn*bv*d FLOP over 12*d*bv bytes =
  ``bn / 2`` FLOP/byte whatever bv;
- d(h): the table once per row block, two products: ``4 * bn / w_size``;
- d(w,b): all rows once per vocab block, two products: ``4 * bv / h_size``.
A v5e's ridge is 197 TFLOP/s over 819 GB/s = 240 FLOP/byte. By that count
the forward and d(h) want rows and d(w,b) wants vocabulary, so each kernel
gets its own (bn, bv) from ``_fit_blocks`` (``_ROWS``, ``_COLS``, with what
the timings added to the count), clamped to the shape and shrunk to the
scoped VMEM there is: Mosaic's default 16 MiB where tiles at the ridge fit it
(d = 1,024 in the forward and the two kernels), else a budget of 40 MiB
under the 48 MiB the call then asks for (the default holds no tile of a
d = 2,048 head that leaves the ridge). The one pass needs 1,024 rows a block
to hide dw's way through HBM (512 FLOP/byte; at 512 rows it sits at the ridge
and runs 12% slower: PERF.md, PR 30) and the VMEM for them, up to
``_BWD_VMEM_BUDGET``; where they do not fit, where the table is not float32
(dw accumulates in the table's own dtype) or where the vocabulary gives
fewer than three blocks (``_MIN_COL_BLOCKS``), the two kernels run, which
share nothing but ``lse`` and ``g``, each padded to the kernel's own row
blocks.

Nothing here scales with N*V, so the fused head trains batches and
vocabularies whose logits cannot exist: V=262k (32 GiB of logits) and N=262k
(16 GiB) both train where the XLA head runs out of memory, and the lm1b
example trains its exact 793,471-word vocabulary with the TRUE softmax
objective (48 GiB of logits if materialized; the reference needed sampled
softmax).

On the CPU backend the kernels run in pallas interpret mode, so the test mesh
exercises the same code path; ``tests/test_chip_compile.py`` compiles them
for a described v5e at the tiles the rule picks.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu import telemetry
from autodist_tpu.ops.blockwise_attention import NEG_INF
from autodist_tpu.ops.flash_attention import _use_interpret
from autodist_tpu.ops.named_call import named_pallas_call

_LANES = 128
# Padding rows' lse: large POSITIVE so exp(logits - lse) underflows to exactly 0
# whatever the bias — padding with 0 would overflow exp for bias values > ~88
# and poison dw/db with NaN through inf * 0.
_PAD_LSE = 1e30


def _logits_tile(h_ref, w_ref, b_ref, w_vd: bool, vi, bv: int, v: int):
    """([bn, bv] f32 logits tile, cast+masked w tile). The single place the
    per-tile activation-dtype cast happens — w is contracted per its stored
    layout with no HBM copy of the table. The arrays are NOT padded to block
    multiples (padding would copy the multi-GiB table every step): the ragged
    last vocab tile reads undefined memory, which is zero-masked on the w side
    (so no garbage inf/NaN can ride a contraction) and -inf-masked in the
    logits (so the softmax never sees the lanes)."""
    wt = w_ref[...].astype(h_ref.dtype)
    col = vi * bv + jax.lax.broadcasted_iota(jnp.int32, wt.shape,
                                             0 if w_vd else 1)
    wt = jnp.where(col < v, wt, jnp.zeros((), wt.dtype))
    dims = (((1,), (1,)), ((), ())) if w_vd else (((1,), (0,)), ((), ()))
    logits = jax.lax.dot_general(h_ref[...], wt, dims,
                                 preferred_element_type=jnp.float32)
    logits = logits + b_ref[0][None, :]
    lane = vi * bv + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    return jnp.where(lane < v, logits, NEG_INF), wt


# ------------------------------------------------------------------- forward

def _fwd_kernel(h_ref, w_ref, b_ref, lse_ref, m_ref, l_ref, *, n_v: int,
                w_vd: bool, bv: int, v: int):
    ni = pl.program_id(0)
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    logits, _ = _logits_tile(h_ref, w_ref, b_ref, w_vd, vi, bv, v)  # [bn, bv]
    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new)
    l_ref[:] = jnp.broadcast_to(
        l_prev * jnp.exp(m_prev - m_new) + p.sum(axis=-1, keepdims=True),
        l_ref.shape)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(vi == n_v - 1)
    def _finish():
        lse_ref[0, ni, :] = m_ref[:, 0] + jnp.log(jnp.maximum(l_ref[:, 0], 1e-30))


def _shapes(h, w, bn, bv, w_vd: bool):
    n, d = h.shape
    v = w.shape[0] if w_vd else w.shape[1]
    return n, d, v, pl.cdiv(n, bn), pl.cdiv(v, bv)


# Scoped VMEM: Mosaic's default limit on v5e is 16 MiB of the chip's 128, and
# under it no tile of a d = 2,048 head is large enough to leave the ridge. A
# kernel whose tiles need more asks for _VMEM_LIMIT (as ops/grouped_matmul.py
# does) and is fitted to _VMEM_BUDGET, the limit less 8 MiB for the spread of
# the temporaries model below against the compiler's own count. One whose
# tiles fit the default asks for nothing: with a raised limit on the head's
# calls (23 to 48 MiB alike) XLA assigns less of the surrounding step to VMEM,
# and the GPT-2 cell's own fusions ran 8 ms a step slower for 6 ms the larger
# tiles saved (PERF.md, PR 28).
_DEFAULT_VMEM_BUDGET = (16 << 20) - (256 << 10)
_VMEM_BUDGET = 40 << 20
_VMEM_LIMIT = _VMEM_BUDGET + (8 << 20)
_BWD_VMEM_BUDGET = 64 << 20
_BWD_VMEM_LIMIT = _BWD_VMEM_BUDGET + (8 << 20)

# What Mosaic allocates beyond the pipeline buffers and scratch: values the
# kernel body materializes in VMEM (the f32 [bn, bv] logits/probability plane
# and its cast for the second matmul, masked and transposed copies of the h
# tile, the masked copy of the w tile). Bytes per element of ([bn, bv] plane,
# [bn, d] h tile, [d, bv] w tile), keyed by kernel and activation itemsize.
# Least upper bounds over both table layouts and both table dtypes of the
# scoped allocations libtpu 0.0.34 reports for v5e at d in {512, 768, 1024,
# 2048}, bn in {256, 512, 1024}, bv in {256, 512, 1024, 2048} (396 compiles,
# PR 28): never under the compiler's count there, over it by 0.6 to 1.5 MiB
# in the mean and 5.4 at most; tests/test_chip_compile.py asks the compiler
# itself. "bwd" (PR 30): over the least limit the one pass compiles under
# (bisected to 0.25 MiB) at d in {512, 1024, 2048}, 1,024 rows whole and
# ragged, bv in {256, 512}, a float32 table in both layouts, 52 shapes:
# never under it, over it by 5.4 MiB in the mean with bfloat16 rows (a
# [V, d] table takes 4 to 7 MiB more than a [d, V] one for the same tiles).
_TEMP_BYTES = {
    ("fwd", 2): (3.5, 2.75, 0.75),
    ("dh", 2): (2.0, 3.25, 2.25),
    ("dw", 2): (4.0, 4.75, 0.0),
    ("bwd", 2): (7.75, 5.0, 0.0),
    ("fwd", 4): (4.25, 0.5, 0.0),
    ("dh", 4): (5.75, 0.25, 0.25),
    ("dw", 4): (4.25, 2.5, 0.0),
    ("bwd", 4): (10.25, 4.25, 3.5),
}


def _vmem_need(kernel: str, d: int, bn: int, bv: int, h_size: int,
               w_size: int) -> float:
    """Scoped VMEM bytes one kernel ("fwd", "dh", "dw" or the one-pass
    "bwd") takes at these tiles: double-buffered input/output tiles, scratch
    accumulators, and the in-kernel temporaries of ``_TEMP_BYTES``. The
    whole-array lse/g planes are not in it: the compiler's count does not
    move with the row count."""
    h_tiles = 2 * bn * d * h_size
    w_tiles = 2 * d * bv * w_size
    if kernel == "fwd":
        # running max + denominator scratch, (bn, LANES) f32 each
        buffers = h_tiles + w_tiles + 2 * 4 * bn * _LANES
    elif kernel == "dh":
        # output [bn, d] tile + f32 [bn, d] accumulator
        buffers = h_tiles + w_tiles + 2 * bn * d * h_size + 4 * bn * d
    elif kernel == "bwd":
        # dh's output tile (one buffer in what the fit counts: its block
        # changes once a sweep) and f32 accumulator, the masked h tile of a
        # ragged last row block (counted whether or not there is one), the
        # dw tile in and out, the (8, bv) f32 db output tile
        buffers = (h_tiles + w_tiles + 2 * bn * d * h_size + 4 * bn * d
                   + 2 * 2 * d * bv * w_size + 2 * 8 * 4 * bv)
    else:
        # dw output tile + f32 dw accumulator + the [_LANES, bv] f32 db
        # accumulator + the (1, bv) db output tile
        buffers = (h_tiles + w_tiles + 2 * d * bv * w_size + 4 * d * bv
                   + 4 * _LANES * bv + 2 * bv * w_size)
    plane, h_tile, w_tile = _TEMP_BYTES[(kernel, 2 if h_size <= 2 else 4)]
    return buffers + plane * bn * bv + h_tile * bn * d + w_tile * d * bv


# The tiles a kernel is given where VMEM allows, found by timing each kernel
# alone on a v5e at 16,384 x 2,048 x 50,304 and 8,192 x 1,024 x 50,257 (bf16
# rows, f32 table; tools/flash_forward_timing.py ``xent-*``):
# - rows: 1,024, or 512 where that is what fits. The table tile is cast to
#   the activation dtype and masked once a grid step in all three kernels
#   (dw casts its resident tile again every row block), a share of the
#   tile's products that falls as 1 / bn, and bn is the FLOP a streamed byte
#   pays for (module docstring): 512 rows are at the chip's ridge in the
#   forward and twice it in dh. From 512 to 1,024 a kernel gains 0 to 2%,
#   at 256 it loses 3%, and at (256, 128), where the default limit left the
#   backward at d = 2,048, 30%.
# - vocabulary: the forward pays 3.2 ns a row and vocab tile for the running
#   max and sum (cross-lane reductions, [bn, 1] columns), so its time is the
#   product's x (1 + 150 / bv): 12% more at 512 than at 1,024. dh and dw are
#   flat from 512 up (four times the ridge in dw) and 1.5% slower at 256.
_ROWS, _FLOOR_ROWS = 1024, 512
_COLS = {"fwd": 1024, "dh": 512, "dw": 512, "bwd": 512}
# The one-pass backward reads the dw tile a row block after it was written,
# n_v grid steps later, through Pallas's own pipeline: the fetch runs a step
# ahead and the write-back a step behind, so with fewer than three vocab
# blocks a fetch would overtake the write-back it depends on.
_MIN_COL_BLOCKS = 3


def _fit_blocks(kernel: str, n: int, d: int, v: int, h_size: int, w_size: int,
                bn: int = None, bv: int = None):
    """(bn, bv) of one kernel ("fwd", "dh", "dw", or the one-pass backward
    "bwd") at this shape: (``_ROWS``, ``_COLS``), or the caller's ``bn`` /
    ``bv``, no larger than the rows and the vocabulary there are, shrunk
    until ``_vmem_need`` fits: rows as far as ``_FLOOR_ROWS`` under Mosaic's
    default limit if that is enough, else under the raised one; below that
    vocabulary first (halving bv leaves the table traffic as it is, halving
    bn doubles the forward's and dh's passes over the table), to one lane
    tile each.

    "bwd" keeps its rows (they are what hides dw's way through HBM: module
    docstring) and shrinks vocabulary alone, and is ``None`` where one pass
    is not to be had and "dh" and "dw" run instead: 1,024-row tiles fit no
    limit, the table is not float32 (dw accumulates in the table's own
    array), or the vocabulary gives fewer than ``_MIN_COL_BLOCKS`` blocks.

    The footprint scales with the model dim, the two dtypes and the tile
    plane, differently in each kernel (dw double-buffers a [d, bv] table tile
    on input AND output beside an f32 accumulator; dh holds three [bn, d] row
    tiles; bwd holds both), so each is fitted alone. Block size only changes
    tiling, not results (beyond fp summation order)."""
    start = (min(bn or _ROWS, -(-n // _LANES) * _LANES),
             min(bv or _COLS[kernel], -(-v // _LANES) * _LANES))
    one_pass = kernel == "bwd"
    budget = _BWD_VMEM_BUDGET if one_pass else _VMEM_BUDGET

    def need(blocks):
        return _vmem_need(kernel, d, *blocks, h_size, w_size)

    def shrunk(blocks, budget, floor):
        blocks = list(blocks)
        for axis in (1, 0):
            while blocks[axis] > floor[axis] and need(blocks) > budget:
                blocks[axis] = max(_LANES, blocks[axis] // 2)
        return tuple(blocks)

    row_floors = (start[0], start[0]) if one_pass else (_FLOOR_ROWS, _LANES)
    floor = (row_floors[0], start[1])
    blocks = shrunk(start, _DEFAULT_VMEM_BUDGET, floor)
    if need(blocks) > _DEFAULT_VMEM_BUDGET:
        blocks = shrunk(shrunk(start, budget, floor), budget,
                        (row_floors[1], _LANES))
    if one_pass:
        fits = (need(blocks) <= budget and w_size == 4
                and -(-v // blocks[1]) >= _MIN_COL_BLOCKS)
        return blocks if fits else None
    if need(blocks) > budget:
        # Refusing here names the cause; the compiler's RESOURCE_EXHAUSTED
        # names an allocation size and nothing the caller can change.
        raise ValueError(
            f"fused_softmax_xent: even the minimum {blocks} tiling of the "
            f"{kernel} kernel needs {need(blocks) / 2**20:.1f} MiB of VMEM "
            f"(budget {budget / 2**20:.0f} MiB) at d={d} with "
            f"{h_size}-byte activations and a {w_size}-byte table; use a "
            f"smaller model dim or the XLA head (fused_head=False)")
    return blocks


def _w_spec(d, bv, w_vd, index2):
    """BlockSpec for one vocab tile of w in its stored layout. ``index2`` maps
    grid coords to the vocab-block index."""
    if w_vd:
        return pl.BlockSpec((bv, d), lambda *a: (index2(*a), 0))
    return pl.BlockSpec((d, bv), lambda *a: (0, index2(*a)))


def _blocks(kernel, h, w, bn, bv, w_vd):
    """``_fit_blocks`` for ``kernel`` at the shapes of these arguments, and
    the compiler parameters its call takes: the raised scoped-VMEM limit
    where the tiles need it. ``None`` where "bwd" has no one pass."""
    n, d = h.shape
    v = w.shape[0] if w_vd else w.shape[1]
    sizes = (h.dtype.itemsize, w.dtype.itemsize)
    blocks = _fit_blocks(kernel, n, d, v, *sizes, bn, bv)
    if blocks is None:
        return None
    need = _vmem_need(kernel, d, *blocks, *sizes)
    if need <= _DEFAULT_VMEM_BUDGET:
        return (*blocks, None)
    limit = _VMEM_LIMIT if need <= _VMEM_BUDGET else _BWD_VMEM_LIMIT
    return (*blocks, pltpu.CompilerParams(vmem_limit_bytes=limit))


def _forward(h, w, b, bn, bv, interpret, w_vd):
    bn, bv, params = _blocks("fwd", h, w, bn, bv, w_vd)
    telemetry.gauge("xent.fwd.block_rows").set(bn)
    telemetry.gauge("xent.fwd.block_cols").set(bv)
    n, d, v, n_n, n_v = _shapes(h, w, bn, bv, w_vd)
    lse = named_pallas_call(
        "xent_fwd",
        functools.partial(_fwd_kernel, n_v=n_v, w_vd=w_vd, bv=bv, v=v),
        grid=(n_n, n_v),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            _w_spec(d, bv, w_vd, lambda i, j: j),
            pl.BlockSpec((1, bv), lambda i, j: (0, j)),
        ],
        # Whole [n_n, bn] plane resident (a [1, bn] block violates TPU tiling);
        # 4 bytes/row — same layout rationale as the flash kernel's lse.
        out_specs=pl.BlockSpec((1, n_n, bn), lambda i, j: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, n_n, bn), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bn, _LANES), jnp.float32),   # running max
            pltpu.VMEM((bn, _LANES), jnp.float32),   # running denominator
        ],
        compiler_params=params,
        interpret=interpret,
    )(h, w, b.reshape(1, -1))
    return lse.reshape(n_n * bn)[:n]


# ------------------------------------------------------------------ backward

def _gp_tile(h_ref, w_ref, b_ref, lse_ref, g_ref, w_vd: bool, ni, vi, bn: int,
             bv: int, n: int, v: int, mask_rows: bool):
    """(d(logits) of one [bn, bv] tile in float32, ``exp(logits - lse) * g``
    on the recomputed logits, and the cast and masked w tile): what every
    product of the backward takes. ``mask_rows``: the tile is contracted over
    its rows (dw, db), so the ragged last row block's undefined rows must be
    hard zeros (g pads to 0, but 0 * garbage-inf logits would be NaN)."""
    logits, wt = _logits_tile(h_ref, w_ref, b_ref, w_vd, vi, bv, v)
    gp = jnp.exp(logits - lse_ref[0, ni, :][:, None]) * g_ref[0, ni, :][:, None]
    if mask_rows:
        gp = _valid_rows(gp, ni, bn, n)
    return gp, wt


def _valid_rows(x, ni, bn: int, n: int):
    """``x`` ([bn, ...], row block ``ni``) with the rows past ``n`` zeroed."""
    row = ni * bn + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(row < n, x, jnp.zeros((), x.dtype))


def _dh_product(gp, wt, w_vd: bool):
    """[bn, d] f32: ``gp @ w_tile^T`` against the tile's stored layout."""
    dims = (((1,), (0,)), ((), ())) if w_vd else (((1,), (1,)), ((), ()))
    return jax.lax.dot_general(gp.astype(wt.dtype), wt, dims,
                               preferred_element_type=jnp.float32)


def _dw_product(rows, gp, w_vd: bool):
    """The dw tile in float32 from the (masked) h tile ``rows`` [bn, d]:
    ``gp^T @ rows`` as [bv, d] for a [V, d] table, ``rows^T @ gp`` as
    [d, bv] for a [d, V] one."""
    gph = gp.astype(rows.dtype)
    operands = (gph, rows) if w_vd else (rows, gph)
    return jax.lax.dot_general(*operands, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _bwd_kernel(h_ref, w_ref, b_ref, lse_ref, g_ref, dw_in_ref, dh_ref, dw_ref,
                db_ref, dh_acc, *rows_ref, n_v: int, w_vd: bool, bn: int,
                bv: int, n: int, v: int, interpret: bool):
    """One pass: grid (row blocks, vocab blocks). The h tile and the f32 dh
    accumulator stay for a sweep over the vocabulary; each step makes the
    logits tile and d(logits) once and from it dh's term, the dw tile and
    db's column sums. The dw tile is [bv, d] whatever the table's layout
    (``_backward_one_pass``). dw accumulates through HBM: ``dw_in_ref`` is
    the dw output itself (aliased), holding what the earlier row blocks left
    in this tile. ``rows_ref``: where the last row block is ragged, the h
    tile with its undefined rows zeroed, made once a row block."""
    ni = pl.program_id(0)
    vi = pl.program_id(1)
    ragged = n % bn != 0
    # Pallas's interpreter hands an aliased input over as a copy made before
    # the first step and loads every output block with the array's current
    # content, so there the output block is what holds the earlier row
    # blocks' sum; on the chip an output block is only ever written.
    left_ref = dw_ref if interpret else dw_in_ref
    rows_ref = rows_ref[0] if ragged else h_ref

    @pl.when(vi == 0)
    def _init():
        dh_acc[:] = jnp.zeros_like(dh_acc)
        if ragged:
            rows_ref[...] = _valid_rows(h_ref[...], ni, bn, n)

    gp, wt = _gp_tile(h_ref, w_ref, b_ref, lse_ref, g_ref, w_vd, ni, vi, bn,
                      bv, n, v, mask_rows=ragged)
    dh_acc[:] += _dh_product(gp, wt, w_vd)
    dw = _dw_product(rows_ref[...], gp, True)

    @pl.when(ni == 0)
    def _first():           # nothing to add to: what the buffer holds is not read
        dw_ref[...] = dw

    @pl.when(ni > 0)
    def _add():
        dw_ref[...] = left_ref[...] + dw

    db_ref[0] = gp.sum(axis=0, keepdims=True)

    @pl.when(vi == n_v - 1)
    def _finish():
        dh_ref[...] = dh_acc[:].astype(dh_ref.dtype)


def _dh_kernel(h_ref, w_ref, b_ref, lse_ref, g_ref, dh_ref, acc_ref, *, n_v: int,
               w_vd: bool, bn: int, bv: int, n: int, v: int):
    ni = pl.program_id(0)
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    gp, wt = _gp_tile(h_ref, w_ref, b_ref, lse_ref, g_ref, w_vd, ni, vi, bn,
                      bv, n, v, mask_rows=False)
    acc_ref[:] += _dh_product(gp, wt, w_vd)                   # [bn, d]

    @pl.when(vi == n_v - 1)
    def _finish():
        dh_ref[...] = acc_ref[:].astype(dh_ref.dtype)


def _dwdb_kernel(h_ref, w_ref, b_ref, lse_ref, g_ref, dw_ref, db_ref,
                 dw_acc, db_acc, *, n_n: int, w_vd: bool, bn: int, bv: int,
                 n: int, v: int):
    vi = pl.program_id(0)
    ni = pl.program_id(1)  # read at top level: program_id is invalid inside when-bodies in interpret mode

    @pl.when(ni == 0)
    def _init():
        dw_acc[:] = jnp.zeros_like(dw_acc)
        db_acc[:] = jnp.zeros_like(db_acc)

    # The dw/db contraction runs over the row (token) axis, so the ragged last
    # row block's undefined rows must be hard zeros on BOTH operands.
    gp, _ = _gp_tile(h_ref, w_ref, b_ref, lse_ref, g_ref, w_vd, ni, vi, bn,
                     bv, n, v, mask_rows=True)
    dw_acc[:] += _dw_product(_valid_rows(h_ref[...], ni, bn, n), gp, w_vd)
    db_acc[:, :] += jnp.broadcast_to(gp.sum(axis=0)[None, :], db_acc.shape)

    @pl.when(ni == n_n - 1)
    def _finish():
        dw_ref[...] = dw_acc[:].astype(dw_ref.dtype)
        db_ref[...] = db_acc[:1, :].astype(db_ref.dtype)


def _row_planes(lse, g, n_n: int, bn: int):
    """``lse`` and ``g`` as the ``[1, n_n, bn]`` planes one backward kernel
    reads whole. They are tiny [N] vectors; padding THEM is cheap (unlike the
    table). Padding rows must contribute nothing: the gradient pads as zero
    AND lse pads large-positive so exp underflows (see _PAD_LSE)."""
    pad = n_n * bn - lse.shape[0]
    lse_p = jnp.pad(lse, (0, pad), constant_values=_PAD_LSE)
    g_p = jnp.pad(g.astype(jnp.float32), (0, pad))
    return lse_p.reshape(1, n_n, bn), g_p.reshape(1, n_n, bn)


def _backward(h, w, b, lse, g, bn, bv, interpret, w_vd):
    fwd_bn, _, _ = _blocks("fwd", h, w, bn, bv, w_vd)
    one_pass = _blocks("bwd", h, w, bn, bv, w_vd)
    telemetry.gauge("xent.bwd.passes").set(1 if one_pass else 2)
    if one_pass:
        rows = one_pass[0]
        grads = _backward_one_pass(h, w, b, lse, g, *one_pass, interpret, w_vd)
    else:
        rows = _blocks("dh", h, w, bn, bv, w_vd)[0]
        grads = _backward_two_kernels(h, w, b, lse, g, bn, bv, interpret, w_vd)
    n = h.shape[0]
    telemetry.gauge("xent.table_passes").set(pl.cdiv(n, fwd_bn) + pl.cdiv(n, rows))
    return grads


def _backward_one_pass(h, w, b, lse, g, bn, bv, params, interpret, w_vd):
    n, d, v, n_n, n_v = _shapes(h, w, bn, bv, w_vd)
    telemetry.gauge("xent.bwd.block_rows").set(bn)
    telemetry.gauge("xent.bwd.block_cols").set(bv)
    row_plane = pl.BlockSpec((1, n_n, bn), lambda i, j: (0, 0, 0))
    # dw is made as [V, d] whatever the table's layout and handed back
    # transposed for a [d, V] table: the layout XLA keeps such a table's
    # gradient in (the true-logit term scatters into its columns), so the
    # transpose is a relabelling where the [d, V] tile was a copy.
    dw_tile = pl.BlockSpec((bv, d), lambda i, j: (j, 0))
    ragged_rows = [pltpu.VMEM((bn, d), h.dtype)] if n % bn else []
    # Under its old name: the benchmark's reader sums xent_bwd_dh + xent_bwd_dw
    # (benchmark/kernel_parts.py), as flash's one pass stayed flash_bwd_dkv.
    dh, dw, db = named_pallas_call(
        "xent_bwd_dw",
        functools.partial(_bwd_kernel, n_v=n_v, w_vd=w_vd, bn=bn, bv=bv, n=n,
                          v=v, interpret=interpret),
        grid=(n_n, n_v),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            _w_spec(d, bv, w_vd, lambda i, j: j),
            pl.BlockSpec((1, bv), lambda i, j: (0, j)),
            row_plane, row_plane,
            dw_tile,
        ],
        out_specs=(
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            dw_tile,
            # a row block's own column sums, summed below: n_n * V floats
            pl.BlockSpec((1, 1, bv), lambda i, j: (i, 0, j)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n, d), h.dtype),
            jax.ShapeDtypeStruct((v, d), w.dtype),
            jax.ShapeDtypeStruct((n_n, 1, v), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((bn, d), jnp.float32), *ragged_rows],
        # dw is its own accumulator: the tile a step reads is the one the
        # row block before wrote, n_v steps earlier (hence _MIN_COL_BLOCKS);
        # what the array holds before the first row block is never read.
        input_output_aliases={5: 1},
        compiler_params=params,
        interpret=interpret,
    )(h, w, b.reshape(1, -1), *_row_planes(lse, g, n_n, bn),
      jax.lax.empty((v, d), w.dtype))
    return dh, dw if w_vd else dw.T, db.sum(axis=(0, 1))


def _backward_two_kernels(h, w, b, lse, g, bn, bv, interpret, w_vd):
    bvec = b.reshape(1, -1)

    # d(h): rows decide how often the table is streamed.
    bn_h, bv_h, params = _blocks("dh", h, w, bn, bv, w_vd)
    n, d, v, n_n, n_v = _shapes(h, w, bn_h, bv_h, w_vd)
    telemetry.gauge("xent.bwd.dh.block_rows").set(bn_h)
    telemetry.gauge("xent.bwd.dh.block_cols").set(bv_h)
    dh = named_pallas_call(
        "xent_bwd_dh",
        functools.partial(_dh_kernel, n_v=n_v, w_vd=w_vd, bn=bn_h, bv=bv_h,
                          n=n, v=v),
        grid=(n_n, n_v),
        in_specs=[
            pl.BlockSpec((bn_h, d), lambda i, j: (i, 0)),
            _w_spec(d, bv_h, w_vd, lambda i, j: j),
            pl.BlockSpec((1, bv_h), lambda i, j: (0, j)),
            pl.BlockSpec((1, n_n, bn_h), lambda i, j: (0, 0, 0)),
            pl.BlockSpec((1, n_n, bn_h), lambda i, j: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bn_h, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), h.dtype),
        scratch_shapes=[pltpu.VMEM((bn_h, d), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
    )(h, w, bvec, *_row_planes(lse, g, n_n, bn_h))

    # d(w, b): vocabulary decides how often the rows are streamed.
    bn_w, bv_w, params = _blocks("dw", h, w, bn, bv, w_vd)
    n, d, v, n_n, n_v = _shapes(h, w, bn_w, bv_w, w_vd)
    telemetry.gauge("xent.bwd.dw.block_rows").set(bn_w)
    telemetry.gauge("xent.bwd.dw.block_cols").set(bv_w)
    dw_shape = (v, d) if w_vd else (d, v)
    dw_scratch = pltpu.VMEM((bv_w, d) if w_vd else (d, bv_w), jnp.float32)
    dw, db = named_pallas_call(
        "xent_bwd_dw",
        functools.partial(_dwdb_kernel, n_n=n_n, w_vd=w_vd, bn=bn_w, bv=bv_w,
                          n=n, v=v),
        grid=(n_v, n_n),
        in_specs=[
            pl.BlockSpec((bn_w, d), lambda j, i: (i, 0)),
            _w_spec(d, bv_w, w_vd, lambda j, i: j),
            pl.BlockSpec((1, bv_w), lambda j, i: (0, j)),
            pl.BlockSpec((1, n_n, bn_w), lambda j, i: (0, 0, 0)),
            pl.BlockSpec((1, n_n, bn_w), lambda j, i: (0, 0, 0)),
        ],
        out_specs=(
            _w_spec(d, bv_w, w_vd, lambda j, i: j),
            pl.BlockSpec((1, bv_w), lambda j, i: (0, j)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct(dw_shape, w.dtype),
            jax.ShapeDtypeStruct((1, v), jnp.float32),
        ),
        scratch_shapes=[
            dw_scratch,
            pltpu.VMEM((_LANES, bv_w), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
    )(h, w, bvec, *_row_planes(lse, g, n_n, bn_w))
    return dh, dw, db[0]


# ----------------------------------------------------------------- public op

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _mls(h, w, b, n_block, v_block, interpret, w_layout):
    lse, _ = _mls_fwd(h, w, b, n_block, v_block, interpret, w_layout)
    return lse


def matmul_logsumexp(h, w, b, n_block: int = None,
                     v_block: int = None,
                     interpret: bool = None, w_layout: str = "dv"):
    """``logsumexp(h @ w + b, axis=-1)`` without materializing the logits.

    h: [N, D] (bf16/f32); w: [D, V] (``w_layout="dv"``, flax Dense kernel) or
    [V, D] (``w_layout="vd"``, reference softmax_w layout); b: [V] or None.
    Returns f32 [N]. Differentiable in h, w, b (custom VJP recomputes logits
    tiles from the saved lse); dw returns in w's stored layout and dtype.
    ``n_block`` / ``v_block``: the tiles every kernel starts from in place of
    its own (``_fit_blocks``); left out, each kernel's follow from the shape.

    Under a mesh of several devices the kernels run per device
    (:func:`autodist_tpu.parallel.mesh.per_device`): each on its rows of
    ``h``, against the whole table.
    """
    from autodist_tpu.parallel.mesh import per_device

    def local(h, w, b):
        return _mls(h, w, b, n_block, v_block, interpret, w_layout)

    return per_device(local, (h, w, b), batched=(True, False, False))


def _w_vd(w_layout: str) -> bool:
    if w_layout not in ("dv", "vd"):
        raise ValueError(f"w_layout must be 'dv' or 'vd', got {w_layout!r}")
    return w_layout == "vd"


def _mls_fwd(h, w, b, n_block, v_block, interpret, w_layout):
    if interpret is None:
        interpret = _use_interpret()
    w_vd = _w_vd(w_layout)
    has_bias = b is not None
    v = w.shape[0] if w_vd else w.shape[1]
    bvec = b if has_bias else jnp.zeros((v,), jnp.float32)
    lse = _forward(h, w, bvec, n_block, v_block, interpret, w_vd)
    return lse, (h, w, bvec, lse, has_bias)


def _mls_bwd(n_block, v_block, interpret, w_layout, res, g):
    if interpret is None:
        interpret = _use_interpret()
    h, w, bvec, lse, has_bias = res
    dh, dw, db = _backward(h, w, bvec, lse, g, n_block, v_block, interpret,
                           _w_vd(w_layout))
    return dh, dw, (db if has_bias else None)


_mls.defvjp(_mls_fwd, _mls_bwd)


def fused_softmax_xent(h, w, targets, b=None, n_block: int = None,
                       v_block: int = None,
                       w_layout: str = "dv") -> jax.Array:
    """Per-row NLL of ``targets`` under ``softmax(h @ w + b)`` — the fused-head
    loss. h: [N, D], w per ``w_layout``, targets: int [N]. Returns f32 [N].

    The lse term runs through the pallas kernels; the true-logit term is a
    gather-einsum XLA handles well (its grad is the row-sparse scatter).
    """
    lse = matmul_logsumexp(h, w, b, n_block, v_block, None, w_layout)
    if _w_vd(w_layout):
        w_true = jnp.take(w, targets, axis=0).astype(h.dtype)   # [N, D]
        true_logit = jnp.einsum("nd,nd->n", h, w_true,
                                preferred_element_type=jnp.float32)
    else:
        w_true = jnp.take(w, targets, axis=1).astype(h.dtype)   # [D, N]
        true_logit = jnp.einsum("nd,dn->n", h, w_true,
                                preferred_element_type=jnp.float32)
    if b is not None:
        true_logit = true_logit + b[targets]
    return lse - true_logit
