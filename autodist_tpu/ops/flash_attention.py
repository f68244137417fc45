"""Flash attention — pallas TPU kernels, forward AND backward.

Forward: grid (batch*heads, q-blocks, k-blocks); VMEM scratch carries the
online-softmax state (running max, denominator, unnormalized accumulator) across
the k dimension of the grid — the [L, L] score matrix never exists. While K and V
of one (batch, head) are within ``_RESIDENT_KV_BYTES`` they are ONE resident
block (one grid step a q block, nothing copied for a block above the diagonal);
longer ones stream in blocks, and a block above the diagonal is neither copied
nor computed. Inside a grid step the block is walked in key tiles whose score
tile is held TRANSPOSED, [keys, queries]: the softmax statistics reduce along
sublanes and are lane-dense [1, q_block] vectors. Each tile runs the body of its
class — plain (below the diagonal, no padded key: no iota, no compare, no
select), masked (crossed by the diagonal or holding the ragged tail) or skipped —
decided from grid indices and the SMEM offsets, so ring attention's traced
offsets classify at run time. A tile alone is a chain (product, maximum,
exponential, sum, product: the MXU and the VPU take turns), so the plain tiles,
which are most of a long walk, are taken FOUR (then two) AT A TIME as one
straight-line block with every score product issued first: the scheduler keeps
the MXU's operations in program order, so a tile's score product runs under its
predecessor's softmax and its value product under its successor's, and inside a
block each tile is cut into two halves of the queries, whose softmaxes are
independent, for the same reason. The updates happen in the walk's order, tile by
tile, whatever is in flight. The per-row logsumexp is emitted as a residual for
the backward pass.

Backward (FlashAttention-2 style): scores are recomputed blockwise from the saved
logsumexp, so nothing quadratic is ever materialized. ONE kernel (device name
``flash_bwd_dkv``) produces dQ, dK and dV: grid (batch*heads, k-blocks); q, dO
and a float32 dQ accumulator of the whole (batch, head) stay in VMEM across the
k blocks, and each grid step walks the q tiles from the first the diagonal lets
its keys see — one recomputed score tile (scores, p, dP, ds) feeds all three
gradients, five products where two kernels ran seven. The tile is held
transposed as in the forward, so every product is a form the forward runs and
lse / D enter as the lane-dense rows they are stored as. Past
``_RESIDENT_DQ_BYTES`` of dQ a (batch, head) (L 49,152 at a key width of 64,
24,576 at 128, 16,384 at latent attention's 192) the same block body runs as
two kernels, each recomputing the tile:

- dK/dV (``flash_bwd_dkv``): grid (batch*heads, k-blocks, q-blocks) — a k block
  accumulates p^T dO and ds^T q across its query blocks in VMEM scratch.
- dQ (``flash_bwd_dq``): grid (batch*heads, q-blocks, k-blocks) — a q block
  accumulates ds k across its key blocks.

A block the diagonal hides is neither computed nor (under static offsets)
copied. The row term D_i = rowsum(dO * O) is precomputed in XLA (elementwise,
fused).

A sliding window (``window=W``: a query sees itself and the ``W - 1`` keys
before it) is a second edge of the same classes: tiles wholly below the band
are skipped as those above the diagonal are (not walked inside a block, not
copied where they are a grid step), tiles the lower edge crosses run the masked
body. A window NARROWER than a key tile (128 keys under 512 x 512 tiles) would
touch two tiles a q block, both masked and an eighth full, so there the walk is
fitted to the band (:func:`_band_span`): the forward cuts the q block into
chunks of 128 queries, each of which meets ONE ``[256 keys, 128 queries]`` tile
out of the resident K/V, the keys that end with the chunk's own, with both
edges of the band inside it; the one-pass backward mirrors it along the
queries, a 128-key chunk of its K/V block against the 256 queries from its own
on, out of the resident q and dO (lse, D and the dQ accumulator in rows of 128
queries). Half of such a tile is visible; every chunk's first products are
issued before any softmax, as in a block of plain tiles. The same online
softmax and the same mask arithmetic at another tile shape, decided from
static ints: a call without a window, or with one at least a tile wide, traces
what it traced before.

Grouped KV heads: K and V keep their ``H_kv`` heads in memory and query head
``n`` reads head ``n // (H / H_kv)`` through the index maps; a group's float32
dK / dV are summed outside the kernels.

Two widths (latent attention): the values may be narrower than the keys (the
accumulator, ``o``, ``dO`` and ``dV`` are the values' wide, ``q``, ``k``,
``dQ`` and ``dK`` the keys', the scale ``1 / sqrt(key width)``), and the keys'
trailing columns may be ONE operand all heads share (``k_shared``, the rotary
key head): the score tile is then the sum of two products, ``q_nope k_nope^T
+ q_rope k_rope^T``, the shared block is read through an index map that
ignores the head, and each head's float32 part of its gradient is summed
outside the kernels as a group's is.

A sink (``sink`` ``[H]`` float32, learned): one more logit a query head in
every query's softmax, with no value. The forward's state starts from ``(m,
l, acc) = (sink_n, 1, 0)`` where it otherwise starts from ``(-inf, 0, 0)``,
so the saved logsumexp includes it; the backward kernels are untouched (``p =
exp(s - lse)`` is then each key's share of a softmax that the sink is part
of, and ``D = rowsum(dO * O)`` stands because the sink's value is zero), and
``d sink_n = - sum_i exp(sink_n - lse_i) D_i`` is elementwise work in XLA on
the two planes the kernels are handed. The three kernels of such a call carry
their own device names (``flash_sink_*``).

Where the operands lie (PR 41). The grid's first axis is the (batch, head)
pair and every operand is addressed through its BlockSpec's index map. An
operand the caller hands as ``[B, L, heads, D]`` is transposed by XLA to ``[B
* heads, L, D]`` before the call and its gradient back after it, as every
operand was until PR 41. One handed as ``[B, L, heads * D]``, a projection's
own rows, is read where it lies wherever ``D`` is whole 128-lane tiles and no
row is padded to a block (:func:`_stays`): head ``n`` is column block ``n`` of
the rows (a KV head's query heads name its block; keys and values that one
projection wrote side by side, ``v`` None, are blocks ``2n`` and ``2n + 1`` of
one array, and dK / dV go back as one), the result and the gradients are
written the same way, and the row term D is a product of ``dO * O`` with the
heads' 0 / 1 columns. The kernels' bodies do not know which: a block is ``[1,
rows, D]`` either way, the tiles and their arithmetic are the same, and which
it is is decided from the operands' ranks and widths alone, before anything
is traced. What XLA does AROUND the call decides which form a caller should
hand (measured in ``kanana-pretrain-16k``, PERF.md §6 "PR 41"): rows it never
touches between a projection and the call cost nothing in place and two
copies a layer transposed; a ``[B, L, heads, D]`` array it turns or norms in
between is computed in the transposed layout for nothing, and asked for as
rows it is copied across in float32 instead.

On non-TPU backends the kernels run in pallas interpret mode, so tests exercise
the same code path on the CPU-sim mesh.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu import telemetry
from autodist_tpu.ops.blockwise_attention import NEG_INF
from autodist_tpu.ops.named_call import named_pallas_call

# The forward's blocks, from stand-alone timings of `_flash_forward` on a TPU v5e
# (jax 0.9.0 / libtpu 0.0.34, bf16, causal, the kernel's own device time;
# tools/flash_forward_timing.py, PERF.md §6 "PR 24"). At B·H 128, L 1,024, D 64
# (GPT-2-medium's call) the row-major kernel this replaced took 1.133 ms: its time
# was not the masking but the softmax statistics of a [queries, keys] tile — two
# cross-lane reductions per 8 queries per tile and [q, 1] column vectors that fill
# a vreg per 8 queries (3.5 ns a query row a tile; narrower key tiles made it
# SLOWER, 3.44 ms at 128 keys). With the tile transposed: 0.518 ms at the old
# 512 x 512 blocks, 0.521 with per-class bodies, 0.455 with K/V resident
# (1,024 rows, one grid step a q block), of which the exact scale on q is 0.016 and
# the class bodies 0.009. q block 512 beats 256 (0.699) and 128 (1.006) although it
# computes 75% of the square for 62.5% / 56%; key tile 512 beats 256 (0.554) and
# 1,024 (0.539). Long context, B·H 64: L 4,096 2.33 ms (was 6.49), L 8,192 8.44 ms
# (was 24.05) resident; streamed in 2,048-row blocks 10.16 ms at L 8,192 (11.56
# before skipped blocks stopped being copied). D 128 at L 2,048: 0.90 ms (1.78).
# Non-causal L 1,024: 0.554 ms (1.406).
#
# The forward's walk and its K/V blocks, from stand-alone timings on the same
# chip (PR 39; same tool, PERF.md §6 "PR 39"; ms a call, parent -> change). A
# needed tile cost its MXU time PLUS its VPU time (a chain: 1.79 us at keys 192 /
# values 128, 1.36 at 128, where the products alone need 1.02 / 0.68), so the
# plain tiles are taken four (then two) at a time as one straight-line block,
# every score product issued first, each tile in two halves of the queries; and
# K/V of a head stay resident up to 4 MiB (1 MiB until then), which ends the
# grid's steps over hidden blocks at every shape a cell runs.
#
#   kanana's call (1 x 16,384 x 32, keys 128 + 64 shared, values 128)
#                                            30.206 -> 21.447   (roofline 14.0)
#   trinity's full layer / nemotron's call (1 x 8,192 x 32 over 4 / 2 of 128)
#                                             5.907 ->  4.515
#   trinity's sliding layers (window 2,048)   3.536 ->  3.004
#   lfm2's call (2 x 8,192 x 32 over 8 of 64) 8.443 ->  7.039
#   B.H 64, L 8,192, D 64                     8.435 ->  7.043
#   B.H 64, L 4,096, D 64                     2.334 ->  2.058
#   B.H 64, L 2,048, D 128                    0.902 ->  0.865
#   non-causal, B.H 128, L 1,024, D 64        0.554 ->  0.439
#   1 x 32,768 x 8 of 128 (streamed)         22.966 -> 17.658 (16.160 in the
#                                             16,384-row blocks that now run)
#   GPT-2-medium's call                       0.454 ->  0.454 (no walk under its
#     diagonal holds two plain tiles: no block is built, the parent's kernel)
#   one ring step at L 4,096 (512-row blocks, one tile a walk)
#                                     3.995 / 4.855 -> 3.995 / 4.856
#
# Of kanana's 8.76 ms the blocks of tiles are 4.81 and resident K/V 3.95 (the
# parent's walk on resident K/V 26.255; blocks of tiles on 2,048-row K/V 26.678;
# K/V rows a grid step 1,024 / 2,048 / 4,096 / 8,192 / all 16,384: 29.704 /
# 26.678 / 24.939 / 22.535 / 21.447). What lost or gave nothing: blocks of two
# only (26.681 at kanana's call, the same; 7.311 against 7.039 at lfm2's);
# blocks of eight before four (21.356 against 21.447; at D 64 6.955 against
# 7.039 and 16.84 MiB of scoped VMEM at L 8,192, which the compiler refuses);
# tiles whole inside a block (28.284 against 26.678; 5.368 against 5.150 at
# trinity's full layer); four pieces of the queries (26.709); a tile that runs
# alone cut in halves too (-1% at 128 wide, +1.8% at GPT-2-medium's call, +3.8%
# on a wholly visible ring step). Not timed, refused by the compiler's static
# schedule of the loop body (bundles a needed tile at kanana's call, parent
# 1,944; PERF.md §6 says how it is read): the scores of tile j + 1 carried
# across loop iterations as values (2,442: the loop's phi copies are 512 vector
# moves a tile) or through a VMEM buffer with a run-time slot (2,140: taken for
# aliased, the product and the softmax are serialized), a raw and a settled
# buffer with static addresses (1,824), half a tile rotated across iterations
# (1,764); two tiles an iteration with two static buffers reached 1,641, the
# block of four without any carried state 1,480.
#
# The walk fitted to a narrow band, from stand-alone timings on the same chip
# (PR 47; same tool, `--shapes mimo-swa,bwd-mimo-swa`, PERF.md §6 "PR 47"; ms a
# call, the kernels' own device time, parent -> change): MiMo-V2.5's sliding
# layer, 1 x 8,192 x 64 query heads over 8 KV heads, keys 192 over values 128, a
# window of 128 and a sink a head. Under 512 x 512 tiles a q block touched two
# masked tiles, an eighth full (31 a head, fill 12.8%); fitted, 64 tiles of
# [256, 128] a head, fill 49.6%.
#
#   flash_sink_fwd       4.076 -> 1.249   (its bytes need 0.46: 11.3% -> 36.8%)
#   flash_sink_bwd_dkv   6.811 -> 3.151   (its bytes need 0.92: 13.5% -> 29.2%)
#   trinity's sliding layers (window 2,048: four key tiles wide, not fitted)
#                        3.025 -> 3.025 forward, 4.971 -> 4.971 backward
#
# What lost: the parent's bodies in blocks picked from the window (q block x key
# tile 128 x 128: 4.918 forward, fill 50%, 4,096 grid steps a call; 256 x 256:
# 3.343, fill 25%; 256 x 128 4.027, 128 x 256 4.572; the backward's q tile x K/V
# block 128 x 128 6.568, 256 x 256 4.849, 256 x 128 5.389, 128 x 256 5.468, 128
# x 512 6.470): a grid step and a one-tile chain for every small tile cost more
# than the masked pairs they spare. What the fitted walk still leaves: eight
# chunks a q block (`--blocks 1024,8192,512`) 1.003 and four chunks of a 256-row
# block 1.840; the backward over K/V blocks of 1,024 rows 2.923, of 256 3.571: a
# grid step costs 0.4 us and a (batch, head) of the backward about 11 (q and dO
# arriving, dQ's 6 MiB zeroed and turned), so larger blocks would give 0.25 ms a
# call each, left where the other calls' blocks are.
#
# The backward's schedule, from stand-alone timings of `_flash_backward` on the same
# chip (same tool, PERF.md §6 "PR 26"; ms a call, the kernels' own device time).
# GPT-2-medium's call, two row-major kernels of 512 x 512 blocks: 0.722 + 0.619 =
# 1.341. One pass on the row-major tile 0.923; the tile transposed 0.834; class
# bodies on top 0.910 and the skipped block uncopied 0.918 (SLOWER: the second body
# costs more than the iota / compare / select it saves); q and dO resident with the
# q tiles walked in the kernel 0.812, with one body for every needed tile 0.793 —
# what runs, 0.796 here. dQ accumulated untransposed 0.845. Tiles (q x k) 256 x 512
# 0.895, 512 x 256 0.876, 1,024 x 512 and 512 x 1,024 0.958; a q tile of 512 with
# 1,024-row K/V blocks 0.982. OLMoE's call (B·H 64, L 4,096, D 128): 4.911 + 4.178 =
# 9.089 -> 4.574 (1,024 x 512: 4.828; 256 x 512: 4.967). L 4,096 at D 64: 8.988 ->
# 4.205; D 128 at L 2,048: 2.381 -> 1.332; non-causal L 1,024: 1.529 -> 0.998; one
# ring step at L 4,096 (traced offsets, f32 out): 9.269 -> 4.237 on the diagonal,
# 12.373 -> 7.265 wholly visible. A needed [512, 512] tile takes 2.07 us for five
# products where the MXU at head_dim 64 (half filled) needs about 1.7.
DEFAULT_Q_BLOCK = 512
DEFAULT_K_BLOCK = 512
_KEY_TILE = 512             # keys a score tile of the forward: [512, bq] f32
_RESIDENT_KV_BYTES = 4 << 20     # K (or V) of one (batch, head) kept in VMEM
# Plain tiles the forward's walk takes as one straight-line block, largest
# first, and the halves of the queries a tile is cut into inside one.
_WALK_GROUPS = (4, 2)
_GROUP_Q_CHUNKS = 2
# The one-pass backward's f32 dQ of one (batch, head): Lq x the KEY width x 4
# bytes, so the limit in positions falls with the width. 4 MiB from PR 29 (L
# 16,384 at head_dim 64, 8,192 at 128: trinity-pretrain-8k's call, one pass 4.99
# ms under a window of 2,048 and 8.46 without, where the two kernels took 10.60
# and 15.55; L 16,384 at head_dim 64: 7.30 against 14.37; PERF.md §6 "PR 29");
# 12 MiB since PR 37: L 16,384 at latent attention's 192 (kanana-pretrain-16k's
# call: one pass 49.3 ms where the two kernels took 87.8; PERF.md §6 "PR 37"),
# 24,576 at 128, 49,152 at 64.
_RESIDENT_DQ_BYTES = 12 << 20
_SMALL_DQ_BYTES = 4 << 20
_LANES = 128                # columns of a lane tile


def _backward_vmem_limit(dq_bytes: int) -> int:
    """Scoped VMEM the one-pass backward asks for: 48 MiB up to PR 29's 4 MiB
    of dQ (every call older than PR 37 compiles what it compiled), 100 MiB
    past it (12 MiB of dQ 192 wide, q, dO and dQ's output twice: the kernel
    needs 57.4 MiB and the compiler refuses it 56)."""
    return (48 << 20) if dq_bytes <= _SMALL_DQ_BYTES else (100 << 20)


# ``jax.ad_checkpoint.checkpoint_name`` of what the forward rule hands the
# backward beside its inputs: the output and the log-sum-exp
KEPT_NAME = "flash_residuals"


def _sub_tile(bk: int) -> int:
    """Keys a tile: the widest of 512/256/128 that divides the K/V block, the
    block itself where none does (a ragged or tiny block is one tile)."""
    for sub in (_KEY_TILE, 256, 128):
        if bk % sub == 0:
            return sub
    return bk


def _is_static(n) -> bool:
    return isinstance(n, (int, np.integer, np.ndarray))


def _scale_is_exact(scale: float) -> bool:
    """A power of two (d = 16, 64, 256): scaling q once is exact in any dtype,
    else the scale goes on the float32 tile or accumulator."""
    return math.frexp(scale)[0] == 0.5


def _tile_start(t, sub: int, n_tiles: int):
    """First row of tile ``t`` of ``n_tiles`` tiles of ``sub`` rows."""
    if n_tiles == 1:
        return 0                                          # the one tile, whatever t
    start = t * sub
    return start if _is_static(start) else pl.multiple_of(start, sub)


def _tile_counts(q_lo, k_lo, valid, bq: int, bk: int, sub: int, causal: bool):
    """``(n_plain, n_need)`` of the ``bk // sub`` key tiles of one (q block,
    K/V block) pair: tiles ``[0, n_plain)`` hold no masked score (the plain
    body), ``[n_plain, n_need)`` are crossed by the diagonal or hold padded
    keys (the masked body), the rest hold nothing the mask keeps (skipped).
    ``q_lo`` / ``k_lo`` are the global positions of the block's first query
    and key, ``valid`` the real keys from the block's first on (None: all of
    them). One definition for the trace-time count (ints in, ints out) and
    the kernel (SMEM scalars and grid indices)."""
    operands = (valid, q_lo, k_lo) if causal else (valid,)
    xp = np if all(x is None or _is_static(x) for x in operands) else jnp
    valid = bk if valid is None else xp.clip(valid, 0, bk)
    n_plain = valid // sub
    n_need = (valid + sub - 1) // sub
    if causal:
        # Keys at or before the first query row are visible to every row; keys
        # after the last row to none.
        n_plain = xp.minimum(n_plain, xp.clip(q_lo - k_lo + 1, 0, bk) // sub)
        n_need = xp.minimum(
            n_need, (xp.clip(q_lo + bq - k_lo, 0, bk) + sub - 1) // sub)
    return n_plain, n_need


def _band_tile_counts(q_lo, k_lo, valid, bq: int, bk: int, sub: int,
                      causal: bool, window):
    """``(n_lo, n_ps, n_pe, n_need)`` of the key tiles of one (q block, K/V
    block) pair under a window of ``window`` keys (a query at ``i`` sees keys
    ``i - window < j <= i``): tiles ``[0, n_lo)`` lie wholly below the band
    (skipped), ``[n_lo, n_ps)`` are crossed by its lower edge (masked),
    ``[n_ps, n_pe)`` hold no masked score (plain), ``[n_pe, n_need)`` are
    crossed by the diagonal or hold padded keys (masked), the rest hold
    nothing the mask keeps. ``window=None`` is :func:`_tile_counts` with an
    empty lower part, in Python ints, so that nothing is emitted for it."""
    n_plain, n_need = _tile_counts(q_lo, k_lo, valid, bq, bk, sub, causal)
    if window is None:
        return 0, 0, n_plain, n_need
    xp = np if all(_is_static(x) for x in (q_lo, k_lo, n_plain, n_need)) else jnp
    gap = q_lo - k_lo - window        # the last key the block's first query cannot see
    n_lo = xp.minimum(xp.clip(gap + 1, 0, bk) // sub, n_need)
    n_ps = xp.clip((xp.clip(gap + bq, 0, bk) + sub - 1) // sub, n_lo, n_need)
    return n_lo, n_ps, xp.clip(n_plain, n_ps, n_need), n_need


def _band_span(window, rows: int, other: int, tile: int) -> int:
    """The fitted walk's tile, or 0 where the walk is the tiles' own. Under a
    window narrower than a score tile (``tile`` rows of the side the walk
    runs along) a tile the band crosses is mostly masked: 128 keys under 512
    x 512 tiles touch two tiles a q block, an eighth of which the mask
    keeps. The walk is then fitted to the band: the block's ``rows`` are cut
    into chunks of 128 (a lane tile), and a chunk meets ONE tile of the rows
    of the other side (``other`` of them in reach) that hold everything it
    sees, as many whole lane tiles as the window and the chunk's own width
    cover: 256 at a window of 128, half of them visible. Static ints alone:
    a call without a window, or with one at least a tile wide, asks nothing
    more than this."""
    if window is None or window >= tile or rows % _LANES or other % _LANES:
        return 0
    return min((pl.cdiv(window - 1, _LANES) + 1) * _LANES, other)


def _band_start(first, other: int, span: int):
    """First row of the ``span``-row tile of the fitted walk whose unclipped
    first row is ``first`` (a multiple of 128, traced or not), held inside
    the ``other`` rows there are: at an end of the sequence the tile covers
    rows the chunk cannot see, which the mask removes like any other."""
    if _is_static(first):
        return int(np.clip(first, 0, other - span))
    return pl.multiple_of(jnp.clip(first, 0, other - span), _LANES)


def _band_key_starts(q_lo, k_lo, bq: int, bk: int, span: int) -> list:
    """The forward's fitted walk of one (q block, K/V block) pair: for every
    128-query chunk of the block the first key, from the block's first on,
    of the one ``[span keys, 128 queries]`` tile it meets, which ENDS with
    the chunk's last query's own key. One definition for the kernel and the
    counts."""
    return [_band_start(q_lo + c + _LANES - span - k_lo, bk, span)
            for c in range(0, bq, _LANES)]


def _band_query_starts(q_lo, k_lo, rows: int, bk: int, span: int) -> list:
    """The backward's fitted walk of one K/V block against the ``rows``
    queries of its (batch, head): for every 128-key chunk of the block the
    first query, from the head's first on, of the one ``[128 keys, span
    queries]`` tile it meets, which STARTS with the chunk's first key's own
    query."""
    return [_band_start(k_lo + c - q_lo, rows, span)
            for c in range(0, bk, _LANES)]


def _attend_block(q_ref, k_ref, v_ref, state, *, q_lo, k_lo, valid, sub: int,
                  causal: bool, scale: float, guard_empty_rows: bool,
                  window=None, ks_ref=None, groups=()):
    """Online-softmax update of ``state = (m [1, bq], l [1, bq], acc [dv, bq])``
    against the VMEM-resident K/V block — the single definition shared by the
    plain forward kernel and the carry variant.

    The score tile is held TRANSPOSED, ``[sub keys, bq queries]``: queries run
    along the lanes, so the row maximum and the row sum reduce along sublanes
    (elementwise across vregs, one short reduce at the end) instead of across
    the 128 lanes of every vreg row, and ``m``, ``l`` and the correction are
    lane-dense ``[1, bq]`` vectors instead of ``[bq, 1]`` columns that fill a
    vreg per 8 queries (module header: that, not the masking, was the
    forward's time). The block is walked in tiles of ``sub`` keys with the
    state carried as values, and each tile runs the body of its class
    (:func:`_tile_counts`). Matmul operands stay in the input dtype (bf16 runs
    the MXU at full rate); accumulation and softmax arithmetic are f32.

    ``q_lo`` / ``k_lo``: global positions of the block's first query and key;
    ``valid``: real keys from the block's first on, None where the K/V rows
    hold no padding. ``guard_empty_rows``: a query may have met no valid key yet (ring offsets,
    a carry that starts at NEG_INF, a window whose first tile the block's
    later queries see nothing of), so a masked score must not read as
    ``exp(NEG_INF - NEG_INF) = 1``. With zero offsets and no window every
    query sees key 0 in its first tile and the guard is dead. ``window``:
    keys a query sees, itself included (None: all before it); the tiles below
    the band are not walked (:func:`_band_tile_counts`). ``ks_ref``: the
    keys' trailing columns where every query head shares them (``[1, bk,
    d_s]``; ``k_ref`` then holds the leading ``d - d_s``): the score tile is
    two products, the second against the shared operand."""
    q = q_ref[0]                                      # [bq, d]
    bq, bk = q.shape[0], k_ref.shape[1]
    # scale once per q block where that is exact, else on the score tile.
    prescale = _scale_is_exact(scale)
    if prescale:
        q = q * jnp.asarray(scale, q.dtype)
    if ks_ref is not None:
        q, q_s = q[:, :k_ref.shape[2]], q[:, k_ref.shape[2]:]
    n_tiles = bk // sub

    def keys_of(j):
        """``(first key, keys)`` of tile ``j`` of the block's ``sub``-key
        tiles, or of the fitted walk's tile, which is handed as that pair."""
        return j if isinstance(j, tuple) else (_tile_start(j, sub, n_tiles), sub)

    def scores(j, cols=None):
        """Tile ``j``'s raw score product(s), for the queries ``cols`` (a
        slice of the block's; None: all of them)."""
        start, size = keys_of(j)
        q_c = q if cols is None else q[cols]
        s = jax.lax.dot_general(
            k_ref[0, pl.ds(start, size), :], q_c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [keys, queries]
        if ks_ref is not None:
            s += jax.lax.dot_general(
                ks_ref[0, pl.ds(start, size), :],
                q_s if cols is None else q_s[cols], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        return s

    def update(j, scores, state, masked: bool, cols=None):
        """The online-softmax update of ``state`` (its columns ``cols``)
        with tile ``j``, given the tile's raw scores: the one definition of
        what a tile does, whoever made its scores and when."""
        m_prev, l_prev, acc = (state if cols is None
                               else [x[:, cols] for x in state])
        start, size = keys_of(j)
        v_t = v_ref[0, pl.ds(start, size), :]
        if not prescale:
            scores = scale * scores
        if masked:
            shape, first = scores.shape, 0 if cols is None else cols.start
            key = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            invalid = None
            if valid is not None:
                invalid = key >= valid - start
            if causal:
                query = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                above = key - query > q_lo + first - k_lo - start
                invalid = above if invalid is None else invalid | above
                if window is not None:
                    invalid |= (key - query
                                <= q_lo + first - k_lo - start - window)
            scores = jnp.where(invalid, NEG_INF, scores)
        m_new = jnp.maximum(m_prev, scores.max(axis=0, keepdims=True))
        correction = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        if masked and guard_empty_rows:
            p = jnp.where(scores <= NEG_INF * 0.5, 0.0, p)
        l_new = l_prev * correction + p.sum(axis=0, keepdims=True)
        acc = acc * correction + jax.lax.dot_general(
            v_t, p.astype(v_t.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [d, queries]
        return m_new, l_new, acc

    def tile(j, state, masked: bool):
        """One tile alone, whole: product, softmax, product, a chain."""
        return update(j, scores(j), state, masked)

    def group(first, state, size: int):
        """``size`` plain tiles from ``first`` on as ONE straight-line block,
        the software pipeline written out: every score product is issued
        first (the MXU takes its operations in program order), so tile
        ``j + 1``'s product runs under tile ``j``'s softmax and a tile's
        value product under its successor's; each tile in
        ``_GROUP_Q_CHUNKS`` pieces of the queries (the softmax is a column's
        own), so a piece's products run under another piece's softmax as
        well. The updates themselves come in the walk's order, tile by
        tile."""
        n_c = _GROUP_Q_CHUNKS
        if n_c == 1 or bq % (128 * n_c):    # pieces of whole lane tiles, else whole
            chunks = [None]
        else:
            chunks = [slice(c * bq // n_c, (c + 1) * bq // n_c)
                      for c in range(n_c)]
        tiles = [first + u for u in range(size)]
        raw = [[scores(j, cols) for cols in chunks] for j in tiles]
        for j, row in zip(tiles, raw):
            parts = [update(j, s, state, False, cols)
                     for cols, s in zip(chunks, row)]
            state = parts[0] if len(parts) == 1 else tuple(
                jnp.concatenate(x, axis=1) for x in zip(*parts))
        return state

    span = _band_span(window, bq, bk, sub)
    if span:
        # The walk fitted to a narrow band: a 128-query chunk meets one tile
        # of ``span`` keys, both edges of the band inside it, and nothing
        # else of the block. Every chunk's score product first, as in a
        # block of plain tiles: the chunks share no state, so one's products
        # run under another's softmax.
        chunks = [slice(c, c + _LANES) for c in range(0, bq, _LANES)]
        tiles = [(start, span) for start in _band_key_starts(
            q_lo, k_lo, bq, bk, span)]
        raw = [scores(j, cols) for j, cols in zip(tiles, chunks)]
        parts = [update(j, s, state, True, cols)
                 for j, s, cols in zip(tiles, raw, chunks)]
        return tuple(jnp.concatenate(x, axis=1) for x in zip(*parts))
    counts = _band_tile_counts(q_lo, k_lo, valid, bq, bk, sub, causal, window)
    return _walk(counts, groups, tile, group, state)


def _walk(counts, groups, tile, group, state):
    """The order of a walk over the key tiles ``[n_lo, n_need)`` of
    ``counts`` (:func:`_band_tile_counts`): the tiles the band's lower edge
    crosses one at a time (``tile(j, state, masked)``), then the plain
    tiles, which are most of a long walk, in the largest blocks of
    ``groups`` they fill (``group(first, state, size)``) and what is left of
    them one at a time, then the tiles the diagonal crosses. Every needed
    tile once, in ascending order, whatever is traced."""
    n_lo, n_ps, n_pe, n_need = counts
    state = _loop(n_lo, n_ps, lambda j, s: tile(j, s, True), state)
    lo = n_ps
    for size in groups:
        n = (n_pe - lo) // size
        state = _loop(0, n, lambda i, s, lo=lo, size=size: group(
            lo + size * i, s, size), state)
        lo = lo + size * n
    state = _loop(lo, n_pe, lambda j, s: tile(j, s, False), state)
    return _loop(n_pe, n_need, lambda j, s: tile(j, s, True), state)


def _valid_keys(lk: int, k_start, bk: int):
    """Real keys from a K/V block's first on, None where no block is padded."""
    return lk - k_start if lk % bk else None


def _loop(lo, hi, body, state):
    """``fori_loop`` that emits nothing for a range known to be empty and no
    loop for a single known tile."""
    if _is_static(lo) and _is_static(hi):
        if hi <= lo:
            return state
        if hi - lo == 1:
            return body(int(lo), state)
    return jax.lax.fori_loop(lo, hi, body, state)


def _flash_kernel(q_ref, k_ref, v_ref, *refs, lk: int, sub: int, causal: bool,
                  scale: float, window=None, groups=(), sink_heads: int = 0):
    # refs: [the keys' shared columns,] [the heads' sinks,] o, lse | acc, m, l
    *given, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    sink_ref = given.pop() if sink_heads else None
    ks_ref = given[0] if given else None
    if sink_heads:      # this grid row's head (read here: not inside a branch)
        head = jax.lax.rem(pl.program_id(0), sink_heads)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        if sink_ref is None:
            m_ref[:] = jnp.full_like(m_ref, NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
        else:
            # The head's sink has joined the softmax before any key: a logit
            # as it is (the scale is the keys'), with no value, so the running
            # maximum starts from it, the denominator from exp(0) and the
            # accumulator from nothing; the log-sum-exp below then holds it.
            m_ref[:] = jnp.full(m_ref.shape, sink_ref[head], m_ref.dtype)
            l_ref[:] = jnp.ones_like(l_ref)

    q_start = qi * bq
    k_start = ki * bk
    # Causal: skip K/V blocks strictly above the diagonal.
    needed = (k_start <= q_start + bq - 1) if causal else True
    if window is not None:          # and K/V blocks wholly below the band
        needed &= k_start + bk - 1 > q_start - window

    @pl.when(needed)
    def _step():
        m_ref[:], l_ref[:], acc_ref[:] = _attend_block(
            q_ref, k_ref, v_ref, (m_ref[:], l_ref[:], acc_ref[:]),
            q_lo=q_start, k_lo=k_start, valid=_valid_keys(lk, k_start, bk),
            sub=sub, causal=causal, scale=scale,
            guard_empty_rows=window is not None, window=window, ks_ref=ks_ref,
            groups=groups)

    @pl.when(ki == n_k - 1)
    def _finish():
        l_fin = jnp.maximum(l_ref[:], 1e-30)                      # [1, bq]
        o_ref[0] = (acc_ref[:] / l_fin).T.astype(o_ref.dtype)     # [bq, dv]
        # Per-row logsumexp residual for the backward pass. Padding query rows get
        # a finite lse too (zero-padded q still attends real keys); the backward is
        # safe for them ONLY because dO is zero-padded there — do not rely on lse
        # being NEG_INF for masked rows. Layout: [bh, n_q, bq] with the whole
        # (n_q, bq) plane as one resident block (TPU tiling forbids a [1, bq]
        # block); each q-block writes its row.
        lse_ref[0, pl.ds(qi, 1), :] = m_ref[:] + jnp.log(l_fin)


def _forward_blocks(lq: int, lk: int, d: int, itemsize: int, q_block, k_block):
    """``(bq, bk, sub)`` of the forward: q rows and K/V rows a grid step, keys
    a score tile. An explicit ``q_block`` / ``k_block`` is the grid's block as
    before. Left to the shape (None), the choice is the one the chip timings
    in the module header justify: the backward's q block (so the lse plane is
    its layout already), and K/V resident for a whole (batch, head) while one
    of them is at most ``_RESIDENT_KV_BYTES`` — one grid step a q block, no
    step and no copy for a block above the diagonal, one walk a q block for
    the blocks of tiles to run in — rounded up to whole key tiles (the tail
    is padding, masked like any ragged tail); past it the most tiles, a power
    of two of them, that the same bytes hold."""
    bq = min(q_block or DEFAULT_Q_BLOCK, lq)
    if k_block is None:
        bk = lk if lk <= _KEY_TILE else pl.cdiv(lk, _KEY_TILE) * _KEY_TILE
        if bk * d * itemsize > _RESIDENT_KV_BYTES:
            bk = _KEY_TILE          # a power of two of tiles, so lengths divide
            while 2 * bk * d * itemsize <= _RESIDENT_KV_BYTES:
                bk *= 2
    else:
        bk = min(k_block, lk)
    return bq, bk, _sub_tile(bk)


def _walk_groups(run: int) -> tuple:
    """Sizes of the straight-line blocks the forward's walk takes its plain
    tiles in, where the longest run of plain tiles a walk can hold is
    ``run``: of ``_WALK_GROUPS`` those a run can fill, so a kernel whose
    walks hold fewer than two plain tiles (K/V of 1,024 rows under the
    diagonal: GPT-2-medium's call) is the one-tile chain and nothing else."""
    return tuple(size for size in _WALK_GROUPS if size <= run)


def _grouped(run: int, groups) -> int:
    """Of a run of ``run`` plain tiles, those whose score product is issued
    under another tile's softmax: all but the first of every block."""
    overlapped = 0
    for size in groups:
        overlapped += run // size * (size - 1)
        run %= size
    return overlapped


def _forward_vmem_limit(kv_bytes: int):
    """Scoped VMEM the forward asks for, from the bytes of one grid step's
    K/V blocks (the shared key columns with them): the compiler's default
    (16 MiB, None here) while two buffers of them are within 4 MiB, as every
    call before PR 39 was; past it the two buffers and 12 MiB for the rest
    (the score tiles of a block of four in flight are 4 MiB, q, o, the
    accumulator and the statistics under 2)."""
    return None if 2 * kv_bytes <= 4 << 20 else 2 * kv_bytes + (12 << 20)


def _walks(lq: int, lk: int, bq: int, bk: int, sub: int, causal: bool,
           window=None) -> tuple:
    """``(needed, plain)`` tiles of every walk, one (q block, K/V block)
    pair, of one (batch, head) under zero offsets. Under the fitted walk
    (:func:`_band_span`) a pair the grid runs takes one tile a 128-query
    chunk, none of them plain."""
    span = _band_span(window, bq, bk, sub)
    walks = []
    for qi in range(pl.cdiv(lq, bq)):
        for ki in range(pl.cdiv(lk, bk)):
            n_lo, n_ps, n_pe, n_need = _band_tile_counts(
                qi * bq, ki * bk, _valid_keys(lk, ki * bk, bk), bq, bk, sub,
                causal, window)
            if span:
                walks.append((bq // _LANES if n_need > n_lo else 0, 0))
            else:
                walks.append((int(n_need - n_lo), int(n_pe - n_ps)))
    return tuple(walks)


def _count_tiles(lq: int, lk: int, bq: int, bk: int, sub: int, causal: bool,
                 window=None):
    """(plain, masked, skipped) score tiles of one (batch, head) under zero
    offsets, at the granularity the body runs them: ``[sub, bq]``, or the
    fitted walk's ``[span, 128]``."""
    walks = _walks(lq, lk, bq, bk, sub, causal, window)
    need, plain = (sum(column) for column in zip(*walks))
    span = _band_span(window, bq, bk, sub)
    a_walk = bq // _LANES * pl.cdiv(bk, span) if span else bk // sub
    return plain, need - plain, len(walks) * a_walk - need


def _kv_group(q, k) -> int:
    """Query heads a KV head serves (1: multi-head attention)."""
    h, h_kv = q.shape[2], k.shape[2]
    if h % h_kv:
        raise ValueError(f"{h} query heads do not divide over {h_kv} KV heads")
    return h // h_kv


def _kv_row(group: int):
    """Grid row of a (batch, query head) -> row of its (batch, KV head) in the
    collapsed K/V: query head ``n`` reads KV head ``n // group``."""
    return (lambda bh: bh) if group == 1 else (lambda bh: bh // group)


def _stays(width: int, rows: bool) -> bool:
    """Whether an operand of ``n`` heads of ``width`` columns is read (or
    written) by the kernels where it lies, ``[B, L, n * width]`` with column
    block ``head`` the head's rows: ``rows`` — the caller handed it so, as a
    projection's own rows (:func:`_given_as_rows`), and no row of the call
    is padded to a block — and the lane rule: a head's columns are whole
    128-lane tiles (a 64- or a 192-wide column block does not lower).
    Static shapes alone. Anything else is transposed to ``[B * n, L, width]``
    by XLA around the kernels, as every operand was before PR 41: a ``[B, L,
    n, width]`` operand always (where the caller turns q and k between the
    projection and this call, XLA does that in the transposed layout, and
    asked for ``[B, L, n * width]`` rows it copies them across in float32
    instead), so such a call traces and lowers what it always did."""
    return rows and width % _LANES == 0


def _given_as_rows(q, k, v) -> tuple:
    """Which of q, k, v the caller handed as ``[B, L, heads * D]`` rows,
    three dimensions, and not as ``[B, L, heads, D]``; ``v`` None (the
    values packed behind the keys) goes with ``k``. The result and the
    gradient of the result are handed as ``v`` is."""
    rows = [x is not None and x.ndim == 3 for x in (q, k, v)]
    return rows[0], rows[1], rows[1] if v is None else rows[2]


def _as_heads(x, n: int):
    """``x`` as ``[B, L, n, D]`` whichever way it was handed (None: None)."""
    return x if x is None or x.ndim == 4 else x.reshape(*x.shape[:2], n, -1)


def _as_given(x, rows: bool):
    """``[B, L, n, D]`` back in the form its operand was handed in."""
    return x.reshape(*x.shape[:2], -1) if rows and x is not None else x


def _head_rows(x, stays: bool):
    """``x`` ``[B, L, n, D]`` as the kernels address it: ``[B, L, n * D]``
    where it stays (:func:`_stays`), else ``[B * n, L, D]``."""
    b, length, n, d = x.shape
    if stays:
        return x.reshape(b, length, n * d)
    return x.transpose(0, 2, 1, 3).reshape(b * n, length, d)


def _head_spec(rows: int, width: int, h: int, stays: bool, block, group: int = 1,
               part=(1, 0)):
    """BlockSpec of ``rows`` x ``width`` of one head out of an operand laid
    out by :func:`_head_rows`, under a grid whose first axis is the (batch,
    query head) pair ``bh`` of ``h`` heads: ``block(*rest of the grid
    indices)`` is the row block, and the head is query head ``bh % h``'s
    own, or with ``group`` > 1 the KV head it reads (:func:`_kv_row`).
    ``part = (n, i)``: a head's columns are ``n`` such blocks and this is
    the ``i``-th (an operand that stays only: keys and values of one packed
    array, :func:`_packed_kv`)."""
    if stays:
        n, i = part

        def index(bh, *ij):
            head = jax.lax.rem(bh, h)
            if group > 1:
                head = jax.lax.div(head, group)
            return jax.lax.div(bh, h), block(*ij), head * n + i if n > 1 else head
    else:
        row = _kv_row(group)

        def index(bh, *ij):
            return row(bh), block(*ij), 0
    return pl.BlockSpec((1, rows, width), index)


def _heads_back(x, b: int, h: int, length: int, stays: bool):
    """A kernel's per-head output back as ``[B, L, h, D]``."""
    if stays:
        return x.reshape(b, length, h, -1)
    return x[:, :length, :].reshape(b, h, length, -1).transpose(0, 2, 1, 3)


def _shared_cols(q, k, k_shared, packed: bool = False) -> int:
    """Trailing key columns every query head shares (0: none); the widths of
    ``q``, ``k`` and ``k_shared`` must add up (``packed``: ``k`` holds a
    head's values after its keys, so it must be wider than they are)."""
    d_s = 0 if k_shared is None else k_shared.shape[-1]
    d_k = q.shape[-1] - d_s
    if k.shape[-1] <= d_k if packed else k.shape[-1] != d_k:
        raise ValueError(
            f"q is {q.shape[-1]} wide, k {k.shape[-1]}"
            + (f" + {d_s} shared" if d_s else "")
            + (" with the values packed behind" if packed else ""))
    return d_s


def _kv_parts(packed: bool) -> tuple:
    """``part`` of :func:`_head_spec` for the keys and for the values."""
    return ((2, 0), (2, 1)) if packed else ((1, 0), (1, 0))


def _packed_kv(k, v, d_k: int, k_in: bool):
    """``(k, v, packed)``. ``v`` None: ``k`` is ``[B, L, H_kv, d_k + dv]``, a
    head's keys then its values, as one projection wrote them. ``packed``
    (``k_in``: keys of this width stay where they lie, :func:`_stays`; and
    the values are as wide): the kernels read the two parts of a head where
    they are, blocks ``2 * head`` and ``2 * head + 1`` of the ``[B, L, H_kv *
    2 * d_k]`` rows, ``k`` comes back as it came and ``v`` as None; any
    other packed array is cut in two here."""
    if v is not None:
        return k, v, False
    if k_in and k.shape[-1] == 2 * d_k:
        return k, None, True
    return k[..., :d_k], k[..., d_k:], False


def _flash_forward(q, k, v, causal: bool, q_block, k_block, interpret: bool,
                   window=None, k_shared=None, kept=lambda x: x, heads=None,
                   sink=None):
    """Returns (out [B, Lq, H, Dv], lse [B*H, n_q, bq] f32). ``k`` / ``v``
    may hold fewer heads than ``q`` (grouped KV heads), ``v`` another width
    than ``q`` and ``k`` (the scale is the key width's), and ``k_shared``
    ``[B, Lk, Ds]`` the keys' trailing columns where all heads share them
    (``k`` then holds the leading ``D - Ds``). ``v`` None: ``k`` holds a
    head's values behind its keys (:func:`_packed_kv`). ``kept``: applied
    to ``(out, lse)`` as the backward will read them (the forward rule's
    ``checkpoint_name``), which for a result that stays is the kernel's own
    ``[B, Lq, H * Dv]`` rows: named as ``[B, Lq, H, Dv]``, XLA copied them
    into that shape's layout for the name's sake. ``heads = (H, H_kv)``:
    needed where an operand is handed as ``[B, L, heads * D]`` rows
    (:func:`_given_as_rows`); the result is then rows as ``v`` is. ``sink``
    ``[H]`` float32: a logit a query head that joins every query's softmax
    and carries no value (:func:`flash_attention`); it reaches the kernel
    whole in SMEM, ``lse`` includes it, and the kernel's device name is
    ``flash_sink_fwd``."""
    q_rows, k_rows, v_rows = _given_as_rows(q, k, v)
    h, h_kv = heads or (q.shape[2], k.shape[2])
    q, k, v = _as_heads(q, h), _as_heads(k, h_kv), _as_heads(v, h_kv)
    b, lq, h, d = q.shape
    lk = k.shape[1]
    d_s = _shared_cols(q, k, k_shared, packed=v is None)
    d_k = d - d_s
    dv = k.shape[3] - d_k if v is None else v.shape[3]
    group = _kv_group(q, k)
    scale = 1.0 / (d ** 0.5)

    bq, bk, sub = _forward_blocks(lq, lk, max(d_k, dv), q.dtype.itemsize,
                                  q_block, k_block)
    # (batch, head) is the grid's first axis. An operand handed as a
    # projection's rows, its heads whole lane tiles wide, stays where it lies
    # and the index maps find the head's columns; any other is collapsed to
    # [B*H, L, D] by XLA.
    whole = lq % bq == 0 and lk % bk == 0
    q_in, k_in, v_in = (_stays(d, whole and q_rows), _stays(d_k, whole and k_rows),
                        _stays(dv, whole and v_rows))
    k, v, packed = _packed_kv(k, v, d_k, k_in)
    qf, kf = _head_rows(q, q_in), _head_rows(k, k_in)
    vf = kf if packed else _head_rows(v, v_in)
    n_q = pl.cdiv(lq, bq)
    if n_q * bq - lq:
        qf = jnp.pad(qf, ((0, 0), (0, n_q * bq - lq), (0, 0)))
    n_k = pl.cdiv(lk, bk)
    if n_k * bk - lk:
        pad = ((0, 0), (0, n_k * bk - lk), (0, 0))
        kf, vf = jnp.pad(kf, pad), jnp.pad(vf, pad)
        if d_s:
            k_shared = jnp.pad(k_shared, pad)

    plain, masked, skipped = _count_tiles(lq, lk, bq, bk, sub, causal, window)
    walks = _walks(lq, lk, bq, bk, sub, causal, window)
    runs = [run for _, run in walks]
    groups = _walk_groups(max(runs))
    telemetry.gauge("flash.fwd.tiles_plain").set(plain)
    telemetry.gauge("flash.fwd.tiles_masked").set(masked)
    telemetry.gauge("flash.fwd.tiles_skipped").set(skipped)
    if _band_span(window, bq, bk, sub):     # all but the first chunk's of a walk
        overlapped = sum(need - 1 for need, _ in walks if need)
    else:
        overlapped = sum(_grouped(run, groups) for run in runs)
    telemetry.gauge("flash.fwd.tiles_overlapped").set(overlapped)
    telemetry.gauge("flash.window").set(window or 0)
    telemetry.gauge("flash.kv_group").set(group)
    telemetry.gauge("flash.d_qk").set(d)
    telemetry.gauge("flash.d_v").set(dv)
    telemetry.gauge("flash.shared_key_cols").set(d_s)
    # q, k, v and o: those XLA transposes around the kernel
    telemetry.gauge("flash.fwd.operands_relaid").set(4 - q_in - k_in - 2 * v_in)

    kernel = functools.partial(_flash_kernel, lk=lk, sub=sub, causal=causal,
                               scale=scale, window=window, groups=groups)
    if sink is not None:
        kernel = functools.partial(kernel, sink_heads=h)
    if causal and n_k > 1:
        # A K/V block above the diagonal names the last one below it again
        # (and one below the band the first one inside it), so its (skipped)
        # grid step copies nothing in.
        def kv_block(i, j):
            j = jnp.minimum(j, ((i + 1) * bq - 1) // bk)
            if window is not None:
                j = jnp.maximum(j, jnp.maximum(i * bq - window + 1, 0) // bk)
            return j
    else:
        def kv_block(i, j):
            return j

    def q_block_of(i, j):
        return i

    k_part, v_part = _kv_parts(packed)
    in_specs = [
        _head_spec(bq, d, h, q_in, q_block_of),
        _head_spec(bk, d_k, h, k_in, kv_block, group, k_part),
        _head_spec(bk, dv, h, v_in, kv_block, group, v_part),
    ]
    operands = (qf, kf, vf)
    if d_s:     # one row a batch entry, whatever the head
        in_specs.append(pl.BlockSpec(
            (1, bk, d_s), lambda bh, i, j: (bh // h, kv_block(i, j), 0)))
        operands += (k_shared,)
    if sink is not None:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands += (sink.astype(jnp.float32),)
    out, lse = named_pallas_call(
        "flash_fwd" if sink is None else "flash_sink_fwd", kernel,
        grid=(b * h, n_q, n_k),
        in_specs=in_specs,
        out_specs=(
            _head_spec(bq, dv, h, v_in, q_block_of),
            # VMEM bound: the whole [n_q, bq] lse plane (one f32 row per query,
            # ~4*Lq bytes) stays resident per grid row in this kernel and both
            # backward kernels, so max single-shard sequence length is capped at
            # roughly VMEM/4 bytes minus block working set — ~1M tokens/shard on
            # 16MB VMEM parts, far beyond the q/k block working set that binds
            # first in practice. Restructure to a per-q-block [bq, LANES] scratch
            # staged out per block if shards ever approach that.
            pl.BlockSpec((1, n_q, bq), lambda bh, i, j: (bh, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, lq, h * dv) if v_in
                                 else (b * h, n_q * bq, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, n_q, bq), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((dv, bq), jnp.float32),  # acc, transposed
            pltpu.VMEM((1, bq), jnp.float32),   # running max
            pltpu.VMEM((1, bq), jnp.float32),   # running denominator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_forward_vmem_limit(
                bk * (d + dv) * q.dtype.itemsize)),
        interpret=interpret,
    )(*operands)

    if v_in:
        return kept((out, lse))
    out, lse = kept((_heads_back(out, b, h, lq, False), lse))
    return _as_given(out, v_rows), lse


def _query_tile_counts(q_lo, k_lo, valid, bq: int, bk: int, sub: int,
                       causal: bool, window=None):
    """``(t_need, t_plain, t_band, t_end)`` of the ``bq // sub`` query tiles
    of one (q block, K/V block) pair of the backward, whose block is walked
    along the QUERIES: tiles ``[0, t_need)`` hold nothing the mask keeps
    (their queries come before every key: skipped, neither computed nor,
    where they are a grid step, copied), ``[t_need, t_plain)`` are crossed by
    the diagonal or meet padded keys, ``[t_plain, t_band)`` hold no masked
    score, ``[t_band, t_end)`` are crossed by the lower edge of a window of
    ``window`` keys, and from ``t_end`` on the queries come more than a window
    after every key (skipped as the first are). Without a window ``t_band =
    t_end = bq // sub`` as Python ints. :func:`_tile_counts` on the mirrored
    pair: with positions negated the queries are the keys of a causal mask
    and the last query tile is the first key tile, so there is one definition
    of the classes."""
    n_t = bq // sub
    if not causal:
        t_need = t_plain = 0
    else:
        n_plain, n_need = _tile_counts(-(k_lo + bk - 1), -(q_lo + bq - 1), None,
                                       bk, bq, sub, True)
        t_need, t_plain = n_t - n_need, n_t - n_plain
    if valid is not None:           # a ragged tail of keys: every query meets it
        xp = np if _is_static(valid) and _is_static(t_plain) else jnp
        t_plain = xp.where(valid < bk, n_t, t_plain)
    if window is None:
        return t_need, t_plain, n_t, n_t
    xp = np if all(_is_static(x) for x in (q_lo, k_lo, t_need, t_plain)) else jnp
    reach = k_lo + window - q_lo    # queries from the block's first on that see key k_lo
    t_end = xp.maximum(xp.clip(reach + bk + sub - 2, 0, bq) // sub, t_need)
    t_band = xp.clip(xp.clip(reach, 0, bq) // sub, t_plain, t_end)
    return t_need, xp.minimum(t_plain, t_end), t_band, t_end


def _backward_block(q_ref, do_ref, lse_ref, dd_ref, k_ref, v_ref,
                    dq_acc, dk_acc, dv_acc, *, row0, q_lo, k_lo, valid,
                    sub: int, causal: bool, scale: float, window=None,
                    ks_ref=None, dks_acc=None):
    """The backward's block math against one VMEM-resident K/V block — the
    single definition shared by the one-pass kernel and the two kernels of the
    split path. The q rows of the grid step are walked in tiles of ``sub``
    queries from the first the mask keeps anything of
    (:func:`_query_tile_counts`; one body for crossed and plain tiles alike:
    a second, unmasked body made the kernel 0.3–2.4% SLOWER on the chip, the
    iota / compare / select costing less than a second loop), and one
    recomputed score tile feeds every accumulator that is not None:
    ``dv_acc`` ``[bk, dv]``, ``dk_acc`` ``[bk, d]`` (dK unscaled unless the
    scale went onto q exactly) and ``dq_acc[t]`` ``[d, sub]``, dQ of tile
    ``t`` transposed and unscaled. ``ks_ref`` ``[1, bk, d_s]``: the keys'
    trailing columns where every query head shares them; ``k_ref`` and
    ``dk_acc`` then hold the leading ``d - d_s``, the score tile is two
    products and ``dks_acc`` ``[bk, d_s]`` takes this head's part of the
    shared columns' gradient.

    The tile is held TRANSPOSED, ``[bk keys, sub queries]``, as the forward
    holds it: ``s^T = k q^T`` and ``dP^T = v dO^T`` contract the head dim of
    both operands, ``dV += p^T dO`` and ``dK += ds^T q`` are plain products
    (row-major they transposed a score-sized tile each), ``dQ^T += k^T ds^T``
    contracts the first axis of the small operand as the forward's ``acc``
    does, and lse and D enter as the lane-dense ``[1, sub]`` rows they are
    stored as. Matmul operands keep the input dtype (MXU rate); p / ds and
    every accumulator are f32, p and ds cast where they enter a product.

    ``row0``: the row of the q rows' first tile in the lse / D planes;
    ``q_lo`` / ``k_lo``: global positions of the first query and key;
    ``valid``: real keys from the block's first on, None where no K/V row is
    padding. Padded QUERY rows need no mask: dO is zero there (so dP, D and
    with them ds are zero, and p meets a zero row of dO). ``window``: keys
    a query sees, itself included; the walk ends with the last tile whose
    queries still see a key of the block."""
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    n_t = bq // sub
    prescale = _scale_is_exact(scale)
    k = k_ref[0]                                          # [bk, d]
    v = v_ref[0]
    d_k = k.shape[1]
    ks = None if ks_ref is None else ks_ref[0]            # [bk, d_s]
    t_need, _, _, t_end = _query_tile_counts(q_lo, k_lo, valid, bq, bk, sub,
                                             causal, window)

    def tile(t, carry):
        start = _tile_start(t, sub, n_t)
        q = q_ref[0, pl.ds(start, sub), :]                # [sub, d]
        do = do_ref[0, pl.ds(start, sub), :]
        if prescale:
            q = q * jnp.asarray(scale, q.dtype)
        if ks is not None:
            q, q_s = q[:, :d_k], q[:, d_k:]
        lse = lse_ref[0, pl.ds(row0 + t, 1), :]           # [1, sub]
        dd = dd_ref[0, pl.ds(row0 + t, 1), :]
        scores = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bk, sub]
        if ks is not None:
            scores += jax.lax.dot_general(
                ks, q_s, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        if not prescale:
            scores = scale * scores
        p = jnp.exp(scores - lse)
        invalid = None
        if valid is not None or causal:
            key = jax.lax.broadcasted_iota(jnp.int32, (bk, sub), 0)
        if valid is not None:
            invalid = key >= valid
        if causal:
            query = jax.lax.broadcasted_iota(jnp.int32, (bk, sub), 1)
            above = key - query > q_lo + start - k_lo
            invalid = above if invalid is None else invalid | above
            if window is not None:
                invalid |= key - query <= q_lo + start - k_lo - window
        if invalid is not None:
            p = jnp.where(invalid, 0.0, p)
        dp = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bk, sub]
        ds = (p * (dp - dd)).astype(q.dtype)
        if dv_acc is not None:
            dv_acc[:] += jnp.dot(p.astype(do.dtype), do,
                                 preferred_element_type=jnp.float32)
            dk_acc[:] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
            if ks is not None:
                dks_acc[:] += jnp.dot(ds, q_s,
                                      preferred_element_type=jnp.float32)
        if dq_acc is not None and ks is None:
            dq_acc[t] += jax.lax.dot_general(
                k, ds, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)       # [d, sub]
        elif dq_acc is not None:
            dq_acc[t, :d_k, :] += jax.lax.dot_general(
                k, ds, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dq_acc[t, d_k:, :] += jax.lax.dot_general(
                ks, ds, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return carry

    if n_t == 1 and not _is_static(t_need):
        run = t_need == 0 if _is_static(t_end) else (t_need == 0) & (t_end == 1)
        pl.when(run)(lambda: tile(0, None))               # a branch, not a loop
    else:
        _loop(t_need, t_end, tile, None)


def _backward_band(q_ref, do_ref, lse_ref, dd_ref, k_ref, v_ref,
                   dq_acc, dk_acc, dv_acc, *, q_lo, k_lo, valid, scale: float,
                   window: int, ks_ref, dks_acc, span: int):
    """:func:`_backward_block` with the walk fitted to a narrow band: every
    128-key chunk of the K/V block meets ONE ``[128 keys, span queries]``
    tile, the queries from its first key's own on, both edges of the band
    inside it (:func:`_band_query_starts`), in place of the two ``[bk, bq]``
    tiles the block touches, an eighth full at a window of 128. The same
    five products a tile, in the same precisions. q and dO are the (batch,
    head)'s whole rows, the lse / D planes and ``dq_acc`` are laid out in
    rows of 128 queries (``[rows // 128, 128]``, ``[rows // 128, d, 128]``),
    so that a tile that starts at any lane tile reads and adds whole rows.
    The products that need the operands alone (scores and dP) of EVERY chunk
    are issued first, straight-line: the chunks share nothing but dQ's rows,
    so one's products run under another's exponentials."""
    rows, bk = q_ref.shape[1], k_ref.shape[1]
    prescale = _scale_is_exact(scale)
    k, v = k_ref[0], v_ref[0]
    d_k = k.shape[1]
    ks = None if ks_ref is None else ks_ref[0]
    contract_width = (((1,), (1,)), ((), ()))
    contract_keys = (((0,), (0,)), ((), ()))
    starts = _band_query_starts(q_lo, k_lo, rows, bk, span)
    chunks = [slice(c, c + _LANES) for c in range(0, bk, _LANES)]

    staged = []
    for keys, start in zip(chunks, starts):
        q = q_ref[0, pl.ds(start, span), :]                   # [span, d]
        do = do_ref[0, pl.ds(start, span), :]
        if prescale:
            q = q * jnp.asarray(scale, q.dtype)
        q, q_s = (q, None) if ks is None else (q[:, :d_k], q[:, d_k:])
        scores = jax.lax.dot_general(
            k[keys], q, contract_width,
            preferred_element_type=jnp.float32)               # [128, span]
        if ks is not None:
            scores += jax.lax.dot_general(
                ks[keys], q_s, contract_width,
                preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v[keys], do, contract_width,
                                 preferred_element_type=jnp.float32)
        staged.append((q, q_s, do, scores, dp))

    for keys, start, (q, q_s, do, scores, dp) in zip(chunks, starts, staged):
        first = start // _LANES          # the tile's first row of the planes
        lse, dd = (jnp.concatenate(
            [ref[0, pl.ds(first + i, 1), :] for i in range(span // _LANES)],
            axis=1) for ref in (lse_ref, dd_ref))             # [1, span]
        if not prescale:
            scores = scale * scores
        p = jnp.exp(scores - lse)
        key = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
        query = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        ahead = q_lo + start - k_lo - keys.start    # the first query, past the first key
        invalid = (key - query > ahead) | (key - query <= ahead - window)
        if valid is not None:
            invalid |= key >= valid - keys.start
        p = jnp.where(invalid, 0.0, p)
        ds = (p * (dp - dd)).astype(q.dtype)
        dv_acc[keys, :] += jnp.dot(p.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
        dk_acc[keys, :] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
        dq = jax.lax.dot_general(k[keys], ds, contract_keys,
                                 preferred_element_type=jnp.float32)  # [d, span]
        if ks is not None:
            dks_acc[keys, :] += jnp.dot(ds, q_s,
                                        preferred_element_type=jnp.float32)
            dq = jnp.concatenate([dq, jax.lax.dot_general(
                ks[keys], ds, contract_keys,
                preferred_element_type=jnp.float32)], axis=0)
        for i in range(span // _LANES):
            dq_acc[first + i] += dq[:, i * _LANES:(i + 1) * _LANES]


def _finish_dkdv(dk_ref, dv_ref, dk_acc, dv_acc, scale: float,
                 dks_ref=None, dks_acc=None):
    # dK's q carried the scale where that is exact
    exact = _scale_is_exact(scale)
    dk_ref[0] = (dk_acc[:] if exact else scale * dk_acc[:]).astype(dk_ref.dtype)
    dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)
    if dks_ref is not None:
        dks_ref[0] = (dks_acc[:] if exact else scale * dks_acc[:]).astype(
            dks_ref.dtype)


def _dkv_parts(refs, at: int, packed: int):
    """``refs`` with the one output ``refs[at]`` ``[1, bk, 2 * packed]`` that
    holds dK and dV side by side (``packed``: the keys' width, 0: two
    outputs as they are) replaced by its two halves, dK's and dV's."""
    if not packed:
        return refs
    dkv = refs[at]
    return (refs[:at] + (dkv.at[:, :, :packed], dkv.at[:, :, packed:])
            + refs[at + 1:])


def _shared_refs(refs, shared: bool, n_out: int):
    """A backward kernel's ``refs`` after q, dO, lse, D, k, v — ``[ks,] outs
    [, dks] | scratch [, dks_acc]`` — as ``(ks_ref, dks_ref, dks_acc, the
    rest)``; ``n_out`` outputs without dks (0: a kernel that writes no
    dK)."""
    if not shared:
        return None, None, None, refs
    ks_ref, refs = refs[0], refs[1:]
    if not n_out:
        return ks_ref, None, None, refs
    return (ks_ref, refs[n_out], refs[-1],
            refs[:n_out] + refs[n_out + 1:-1])


def _flash_bwd_kernel(off_ref, q_ref, do_ref, lse_ref, dd_ref, k_ref, v_ref,
                      *refs, lk: int, sub: int, causal: bool, scale: float,
                      window=None, shared: bool = False, packed: int = 0,
                      span: int = 0):
    """The one-pass backward: q, dO and the float32 dQ accumulator of one
    (batch, head) stay in VMEM across its K/V blocks (the grid's second axis);
    a grid step finishes dK and dV of its block, the last writes dQ. ``sub``:
    the queries a row of the lse / D planes and of the accumulator holds, the
    q tile, or 128 under the fitted walk (``span``, :func:`_band_span`)."""
    ks_ref, dks_ref, dks_acc, refs = _shared_refs(refs, shared,
                                                  2 if packed else 3)
    dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = _dkv_parts(refs, 1, packed)
    ki = pl.program_id(1)
    bk = k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    dk_acc[:] = jnp.zeros_like(dk_acc)
    dv_acc[:] = jnp.zeros_like(dv_acc)
    if shared:
        dks_acc[:] = jnp.zeros_like(dks_acc)
    k_start = ki * bk
    block = dict(q_lo=off_ref[0], k_lo=off_ref[1] + k_start,
                 valid=_valid_keys(lk, k_start, bk), scale=scale, window=window,
                 ks_ref=ks_ref, dks_acc=dks_acc)
    if span:
        _backward_band(q_ref, do_ref, lse_ref, dd_ref, k_ref, v_ref,
                       dq_acc, dk_acc, dv_acc, span=span, **block)
    else:
        _backward_block(q_ref, do_ref, lse_ref, dd_ref, k_ref, v_ref,
                        dq_acc, dk_acc, dv_acc, row0=0, sub=sub, causal=causal,
                        **block)
    _finish_dkdv(dk_ref, dv_ref, dk_acc, dv_acc, scale, dks_ref, dks_acc)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finish():
        n_t = q_ref.shape[1] // sub

        def turn(t, carry):
            dq_ref[0, pl.ds(_tile_start(t, sub, n_t), sub), :] = (
                scale * dq_acc[t]).T.astype(dq_ref.dtype)
            return carry

        _loop(0, n_t, turn, None)


def _flash_bwd_dkdv_kernel(off_ref, q_ref, do_ref, lse_ref, dd_ref, k_ref, v_ref,
                           *refs, lk: int, causal: bool, scale: float,
                           window=None, shared: bool = False, packed: int = 0):
    """The split path's dK/dV: a K/V block accumulates over the q blocks."""
    ks_ref, dks_ref, dks_acc, refs = _shared_refs(refs, shared,
                                                  1 if packed else 2)
    dk_ref, dv_ref, dk_acc, dv_acc = _dkv_parts(refs, 0, packed)
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        if shared:
            dks_acc[:] = jnp.zeros_like(dks_acc)

    k_start = ki * bk
    _backward_block(q_ref, do_ref, lse_ref, dd_ref, k_ref, v_ref,
                    None, dk_acc, dv_acc, row0=qi, q_lo=off_ref[0] + qi * bq,
                    k_lo=off_ref[1] + k_start,
                    valid=_valid_keys(lk, k_start, bk), sub=bq, causal=causal,
                    scale=scale, window=window, ks_ref=ks_ref, dks_acc=dks_acc)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finish():
        _finish_dkdv(dk_ref, dv_ref, dk_acc, dv_acc, scale, dks_ref, dks_acc)


def _flash_bwd_dq_kernel(off_ref, q_ref, do_ref, lse_ref, dd_ref, k_ref, v_ref,
                         *refs, lk: int, causal: bool, scale: float,
                         window=None, shared: bool = False):
    """The split path's dQ: a q block accumulates over the K/V blocks."""
    ks_ref, _, _, (dq_ref, dq_acc) = _shared_refs(refs, shared, 0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    k_start = ki * bk
    _backward_block(q_ref, do_ref, lse_ref, dd_ref, k_ref, v_ref,
                    dq_acc, None, None, row0=qi, q_lo=off_ref[0] + qi * bq,
                    k_lo=off_ref[1] + k_start,
                    valid=_valid_keys(lk, k_start, bk), sub=bq, causal=causal,
                    scale=scale, window=window, ks_ref=ks_ref)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = (scale * dq_acc[0]).T.astype(dq_ref.dtype)


def _backward_blocks(lq: int, lk: int, q_block, k_block):
    """``(bq, bk)`` of the backward: queries and keys a score tile (bk is also
    the K/V rows a grid step). An explicit ``q_block`` / ``k_block`` is what
    the caller asked. Left to the shape (None): 512 x 512, which the chip
    timings in the module header chose at head_dim 64 and 128 alike, so D and
    the dtype do not enter; bq is also the forward's q block, whose lse plane
    is then the backward's layout."""
    return min(q_block or DEFAULT_Q_BLOCK, lq), min(k_block or DEFAULT_K_BLOCK, lk)


def _count_backward_tiles(n_q: int, lk: int, bq: int, bk: int, causal: bool,
                          window=None, span: int = 0):
    """(plain, masked, skipped) ``[bk, bq]`` score tiles of one (batch, head)
    under zero offsets; under the fitted walk (``span``) its ``[128, span]``
    tiles, one a 128-key chunk, none of them plain."""
    n_k = pl.cdiv(lk, bk)
    if span:
        need = n_k * bk // _LANES
        return 0, need, need * (pl.cdiv(n_q * bq, span) - 1)
    plain = need = 0
    for ki in range(n_k):
        t_need, t_plain, t_band, t_end = _query_tile_counts(
            0, ki * bk, _valid_keys(lk, ki * bk, bk), n_q * bq, bk, bq, causal,
            window)
        plain, need = plain + int(t_band - t_plain), need + int(t_end - t_need)
    return plain, need - plain, n_q * n_k - need


def prepare_backward_q_side(q, o, g, q_block, in_place=(False, False)):
    """Query-side backward layout: transposed/padded q and dO plus the row term
    D_i = rowsum(dO * O) in the kernels' [bh, n_q, bq] plane layout. Depends only
    on the query side, so ring attention computes it ONCE and reuses it across
    every ring step. ``in_place`` (:func:`_flash_backward` alone): whether q,
    and whether dO and O, are left where they lie (:func:`_stays`); of the
    latter only D's rows change places."""
    b, lq, h, d = q.shape
    qf = _head_rows(q, in_place[0])
    if in_place[1]:
        dof = _head_rows(g, True)
        # A head's sum is 128 lanes of a [B, L, H * Dv] row: as a product
        # with the heads' 0 / 1 columns (exact in three bfloat16 passes, the
        # sum float32), which XLA runs on the rows where they lie; the sum
        # over the last axis of [B, L, H, Dv] made it copy dO and O into a
        # layout of their own first.
        dv = g.shape[-1]
        ones = jnp.repeat(jnp.eye(h, dtype=jnp.float32), dv, axis=0)   # [H*Dv, H]
        dd = jnp.einsum(
            "blc,ch->bhl",
            dof.astype(jnp.float32) * _head_rows(o, True).astype(jnp.float32),
            ones, precision=jax.lax.Precision.HIGHEST).reshape(b * h, lq)
    else:
        dof = g.transpose(0, 2, 1, 3).reshape(b * h, lq, -1)    # the values' width
        of = o.transpose(0, 2, 1, 3).reshape(b * h, lq, -1)
        # D_i = rowsum(dO * O) — elementwise, XLA fuses it.
        dd = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1)

    bq = min(q_block, lq)
    n_q = pl.cdiv(lq, bq)
    q_pad = n_q * bq - lq
    if q_pad:
        qf = jnp.pad(qf, ((0, 0), (0, q_pad), (0, 0)))
        dof = jnp.pad(dof, ((0, 0), (0, q_pad), (0, 0)))   # zero dO kills pad rows
        dd = jnp.pad(dd, ((0, 0), (0, q_pad)))
    dd = dd.reshape(b * h, n_q, bq)                        # lse's [bh, n_q, bq] layout
    return qf, dof, dd, bq, n_q


def _flash_backward_kv(qf, dof, lse, dd, k, v, causal, bq, n_q, k_block,
                       interpret, q_shape, q_offset=0, k_offset=0,
                       out_dtype=None, window=None, k_shared=None,
                       stays=(False, False, False), sink: bool = False):
    """Backward against one K/V shard from prepared query-side layout. Returns
    (dq, dk, dv) in [B, L, H, D] (``v`` None, the values packed behind the
    keys: ``(dq, dkv, None)``; and the shared key columns' gradient ``[B,
    Lk, Ds]`` after them where ``k_shared`` is given); ``out_dtype`` overrides
    the kernels' output dtype (ring passes f32 so per-step contributions
    accumulate unquantized).

    One pass (a single kernel, named ``flash_bwd_dkv``) while the float32 dQ
    accumulator of one (batch, head) is within ``_RESIDENT_DQ_BYTES``; past
    it the same block body in two kernels, dK/dV and ``flash_bwd_dq``, each
    recomputing the score tiles. ``bq`` is the q tile and the lse / D planes'
    row, ``k_block`` the K/V rows a grid step.

    Grouped KV heads (``k`` / ``v`` with fewer heads than q): every query
    head's kernels read the K/V rows of its KV head through the index map and
    write their own float32 dK / dV, which are summed over the group here;
    the shared key columns' float32 parts likewise, over all the heads.

    ``stays``: whether q (as the query side was prepared), the keys and the
    values with dO stay where they lie (:func:`_stays`) and are read, and
    their gradients written, as ``[B, L, heads * width]``; the rest as ``[B *
    heads, L, width]`` through XLA's transposes. A group's dK / dV leave the
    kernels as ``[B * heads, Lk, width]`` either way: their sum over the
    group is a sum over leading dimensions there, where over column blocks
    of one row XLA copied the float32 array into another layout first.

    ``sink``: the forward's softmax held a sink a head (``lse`` includes it).
    The kernels' arithmetic is the same (``p = exp(s - lse)``, ``dS = p (dP -
    D)``: the sink has no value, so ``D = rowsum(dO * O)`` stands); their
    device names are ``flash_sink_bwd_dkv`` / ``flash_sink_bwd_dq``, so that a
    trace tells such a layer's time from another's."""
    name = "flash_sink_bwd_" if sink else "flash_bwd_"
    b, lq, h, d = q_shape
    lk, h_kv = k.shape[1], k.shape[2]
    d_s = 0 if k_shared is None else k_shared.shape[-1]
    d_k = d - d_s
    one_array = v is None   # the values behind the keys: dK and dV go back so
    q_in, k_in, v_in = stays
    k, v, packed = _packed_kv(k, v, d_k, k_in)
    dv = d_k if packed else v.shape[3]
    group = h // h_kv
    scale = 1.0 / (d ** 0.5)
    # dK and dV of a query head: where K and V lie, or one array a group
    dk_in, dv_in, packed_out = (x and group == 1 for x in (k_in, v_in, packed))
    static_offsets = _is_static(q_offset) and _is_static(k_offset)
    offs = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(k_offset, jnp.int32)])

    kf = _head_rows(k, k_in)
    vf = kf if packed else _head_rows(v, v_in)
    bk = min(k_block, lk)
    n_k = pl.cdiv(lk, bk)
    k_pad = n_k * bk - lk
    if k_pad:
        pad = ((0, 0), (0, k_pad), (0, 0))
        kf, vf = jnp.pad(kf, pad), jnp.pad(vf, pad)
        if d_s:
            k_shared = jnp.pad(k_shared, pad)
    dq_dtype = out_dtype or qf.dtype
    dk_dtype = out_dtype or k.dtype
    dv_dtype = out_dtype or (k if packed else v).dtype
    # a group's dK / dV leave the kernels unrounded and are summed below
    head_dk, head_dv = ((dk_dtype, dv_dtype) if group == 1
                        else (jnp.float32, jnp.float32))
    lq_p = n_q * bq
    dq_bytes = lq_p * d * 4
    one_pass = dq_bytes <= _RESIDENT_DQ_BYTES

    # the walk fitted to a narrow band: the one pass under zero offsets
    span = 0
    if one_pass and static_offsets and q_offset == 0 == k_offset:
        span = _band_span(window, bk, lq_p, bq)
    plain, masked, skipped = _count_backward_tiles(n_q, lk, bq, bk, causal,
                                                   window, span)
    telemetry.gauge("flash.bwd.passes").set(1 if one_pass else 2)
    telemetry.gauge("flash.bwd.tiles_plain").set(plain)
    telemetry.gauge("flash.bwd.tiles_masked").set(masked)
    telemetry.gauge("flash.bwd.tiles_skipped").set(skipped)
    # q, dO, k, v, dQ, dK and dV: those XLA transposes around the kernels
    telemetry.gauge("flash.bwd.operands_relaid").set(
        7 - 2 * q_in - 2 * k_in - 3 * v_in)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kernel_args = dict(lk=lk, causal=causal, scale=scale, window=window)
    if d_s:
        kernel_args["shared"] = True
    if packed_out:
        kernel_args["packed"] = d_k
    shared_in = (k_shared,) if d_s else ()
    k_part, v_part = _kv_parts(packed)

    def per_head(rows, width, dtype, stays):
        return jax.ShapeDtypeStruct(
            (b, rows, h * width) if stays else (b * h, rows, width), dtype)

    dq_shape = per_head(lq_p, d, dq_dtype, q_in)
    # dK and dV of a query head: two outputs, or one where they are packed
    dkv_shape = ((per_head(n_k * bk, 2 * d_k, head_dk, True),) if packed_out
                 else (per_head(n_k * bk, d_k, head_dk, dk_in),
                       per_head(n_k * bk, dv, head_dv, dv_in)))
    dkv_scratch = [pltpu.VMEM((bk, d_k), jnp.float32),
                   pltpu.VMEM((bk, dv), jnp.float32)]
    # a head's part of the shared columns' gradient, summed over heads below
    dks_shape = (jax.ShapeDtypeStruct((b * h, n_k * bk, d_s), jnp.float32),) \
        if d_s else ()
    dks_scratch = [pltpu.VMEM((bk, d_s), jnp.float32)] if d_s else []
    if one_pass:
        def all_rows(i):
            return 0

        def own(i):
            return i

        sub = bq        # the queries a row of the planes and of dQ's scratch
        if span:
            kernel_args["span"], sub = span, _LANES
            lse, dd = (x.reshape(b * h, lq_p // sub, sub) for x in (lse, dd))
        q_all = _head_spec(lq_p, d, h, q_in, all_rows)
        do_all = _head_spec(lq_p, dv, h, v_in, all_rows)
        rows = pl.BlockSpec((1, lq_p // sub, sub), lambda bh, i: (bh, 0, 0))
        k_spec = _head_spec(bk, d_k, h, k_in, own, group, k_part)
        v_spec = _head_spec(bk, dv, h, v_in, own, group, v_part)
        dkv_specs = ((_head_spec(bk, 2 * d_k, h, True, own),) if packed_out
                     else (_head_spec(bk, d_k, h, dk_in, own),
                           _head_spec(bk, dv, h, dv_in, own)))
        ks_in = [pl.BlockSpec((1, bk, d_s), lambda bh, i: (bh // h, i, 0))] \
            if d_s else []
        ks_out = (pl.BlockSpec((1, bk, d_s), lambda bh, i: (bh, i, 0)),) \
            if d_s else ()
        dq, *dkv = named_pallas_call(
            name + "dkv",
            functools.partial(_flash_bwd_kernel, sub=sub, **kernel_args),
            grid=(b * h, n_k),
            in_specs=[smem, q_all, do_all, rows, rows, k_spec, v_spec] + ks_in,
            out_specs=(q_all,) + dkv_specs + ks_out,
            out_shape=(dq_shape,) + dkv_shape + dks_shape,
            scratch_shapes=[pltpu.VMEM((lq_p // sub, d, sub), jnp.float32)]
            + dkv_scratch + dks_scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_backward_vmem_limit(dq_bytes)),
            interpret=interpret,
        )(offs, qf, dof, lse, dd, kf, vf, *shared_in)
    else:
        # A block the diagonal hides (or the band's lower edge) names the
        # nearest one it does not, so its (skipped) grid step copies nothing
        # in; traced offsets (the ring) cannot enter an index map and copy
        # every block.
        skip = causal and static_offsets

        def q_of(i, j):      # dK/dV's grid: K/V block i, q block j
            if not skip:
                return j
            first = (k_offset + i * bk - q_offset) // bq
            if window is None:
                return jnp.clip(first, j, n_q - 1)
            last = (k_offset + (i + 1) * bk - 2 + window - q_offset) // bq
            return jnp.clip(j, first, jnp.minimum(last, n_q - 1))

        def k_of(i, j):      # dQ's grid: q block i, K/V block j
            if not skip:
                return j
            last = (q_offset + (i + 1) * bq - 1 - k_offset) // bk
            if window is None:
                return jnp.clip(last, 0, j)
            first = jnp.maximum(q_offset + i * bq - window + 1 - k_offset, 0) // bk
            return jnp.clip(j, jnp.minimum(first, n_k - 1), jnp.maximum(last, 0))

        def own(i, j):       # a grid row's own block, q's or K/V's
            return i

        def q_side(width, stays, block):
            return _head_spec(bq, width, h, stays, block)

        def kv_side(width, stays, block, group=1, part=(1, 0)):
            return _head_spec(bk, width, h, stays, block, group, part)

        rows = pl.BlockSpec((1, n_q, bq), lambda bh, i, j: (bh, 0, 0))
        ks_in = [pl.BlockSpec((1, bk, d_s), lambda bh, i, j: (bh // h, i, 0))] \
            if d_s else []
        dkv = named_pallas_call(
            name + "dkv",
            functools.partial(_flash_bwd_dkdv_kernel, **kernel_args),
            grid=(b * h, n_k, n_q),
            in_specs=[smem, q_side(d, q_in, q_of), q_side(dv, v_in, q_of),
                      rows, rows, kv_side(d_k, k_in, own, group, k_part),
                      kv_side(dv, v_in, own, group, v_part)] + ks_in,
            out_specs=((kv_side(2 * d_k, True, own),) if packed_out else
                       (kv_side(d_k, dk_in, own), kv_side(dv, dv_in, own)))
            + ((kv_side(d_s, False, own),) if d_s else ()),
            out_shape=dkv_shape + dks_shape,
            scratch_shapes=dkv_scratch + dks_scratch,
            interpret=interpret,
        )(offs, qf, dof, lse, dd, kf, vf, *shared_in)

        ks_in = [pl.BlockSpec(
            (1, bk, d_s), lambda bh, i, j: (bh // h, k_of(i, j), 0))] \
            if d_s else []
        kernel_args.pop("packed", None)     # dQ's kernel writes no dK / dV
        dq = named_pallas_call(
            name + "dq",
            functools.partial(_flash_bwd_dq_kernel, **kernel_args),
            grid=(b * h, n_q, n_k),
            in_specs=[smem, q_side(d, q_in, own), q_side(dv, v_in, own),
                      rows, rows, kv_side(d_k, k_in, k_of, group, k_part),
                      kv_side(dv, v_in, k_of, group, v_part)] + ks_in,
            out_specs=q_side(d, q_in, own),
            out_shape=dq_shape,
            scratch_shapes=[pltpu.VMEM((1, d, bq), jnp.float32)],
            interpret=interpret,
        )(offs, qf, dof, lse, dd, kf, vf, *shared_in)

    dq = _heads_back(dq, b, h, lq, q_in)

    def kv_heads(x, dtype, stays):   # a query head's rows -> [B, Lk, H_kv, D]
        if group == 1:
            return _heads_back(x, b, h, lk, stays)
        width = x.shape[-1]             # [B*H, Lk, D]: stays only at group 1
        x = x[:, :lk, :].reshape(b, h_kv, group, lk, width).sum(axis=2)
        return x.astype(dtype).transpose(0, 2, 1, 3)

    n_dkv = 1 if packed_out else 2
    dkv, dks = dkv[:n_dkv], dkv[n_dkv:]
    if packed_out:
        grads = dq, kv_heads(dkv[0], dk_dtype, True), None
    else:
        grads = (dq, kv_heads(dkv[0], dk_dtype, dk_in),
                 kv_heads(dkv[1], dv_dtype, dv_in))
        if one_array:
            grads = dq, jnp.concatenate(grads[1:], axis=-1), None
    if d_s:
        grads += (dks[0][:, :lk, :].reshape(b, h, lk, d_s).sum(axis=1).astype(
            out_dtype or k_shared.dtype),)
    return grads


def _flash_backward(q, k, v, o, lse, g, causal, q_block, k_block, interpret,
                    q_offset=0, k_offset=0, out_dtype=None, window=None,
                    k_shared=None, heads=None, sink=None):
    """(dq, dk, dv[, dks][, dsink]) of one call from its forward's operands
    and residuals, each in the form its operand was handed in
    (:func:`_given_as_rows`; ``o`` and ``g`` as ``v``). ``sink`` ``[H]``:
    the forward's, whose gradient ``d sink_n = - sum_i exp(sink_n - lse_i)
    D_i`` (the sink's share of row ``i``'s softmax times ``-D_i``, its value
    being zero) is elementwise work on what the kernels are handed anyway."""
    q_rows, k_rows, v_rows = _given_as_rows(q, k, v)
    h, h_kv = heads or (q.shape[2], k.shape[2])
    q, k, v = _as_heads(q, h), _as_heads(k, h_kv), _as_heads(v, h_kv)
    o, g = _as_heads(o, h), _as_heads(g, h)
    bq, bk = _backward_blocks(q.shape[1], k.shape[1], q_block, k_block)
    whole = q.shape[1] % bq == 0 and k.shape[1] % bk == 0
    d_k = q.shape[3] - (0 if k_shared is None else k_shared.shape[-1])
    stays = (_stays(q.shape[3], whole and q_rows), _stays(d_k, whole and k_rows),
             _stays(g.shape[3], whole and v_rows))
    qf, dof, dd, bq, n_q = prepare_backward_q_side(q, o, g, bq,
                                                   (stays[0], stays[2]))
    grads = _flash_backward_kv(qf, dof, lse, dd, k, v, causal, bq, n_q, bk,
                               interpret, q.shape, q_offset=q_offset,
                               k_offset=k_offset, out_dtype=out_dtype,
                               window=window, k_shared=k_shared, stays=stays,
                               sink=sink is not None)
    if sink is not None:
        with jax.named_scope("attn.sink_grad"):
            b, h = q.shape[0], q.shape[2]
            share = jnp.exp(sink.astype(jnp.float32)[None, :, None]
                            - lse.reshape(b, h, -1))     # padded rows: D is 0
            grads += ((-jnp.sum(share * dd.reshape(b, h, -1), axis=(0, 2))
                       ).astype(sink.dtype),)
    return tuple(_as_given(x, rows) for x, rows in zip(
        grads, (q_rows, k_rows, v_rows))) + grads[3:]


def _flash_carry_kernel(off_ref, q_ref, k_ref, v_ref, acc_in_ref, m_in_ref,
                        l_in_ref, acc_out_ref, m_out_ref, l_out_ref,
                        acc_sc, m_sc, l_sc, *,
                        lk: int, q_block: int, k_block: int, sub: int,
                        causal: bool, scale: float):
    """Forward kernel with online-softmax carry in/out (ring attention's local
    step): identical block math to :func:`_flash_kernel`, but the (acc, m, l)
    state initializes from the carry inputs and is emitted UNNORMALIZED so
    partial results merge across ring steps (the scratch-carried state IS the
    ring merge state — no extra merge pass needed)."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)
    q_off = off_ref[0]
    k_off = off_ref[1]

    @pl.when(ki == 0)
    def _init():
        acc_sc[:] = acc_in_ref[0].T
        m_sc[:] = m_in_ref[0, pl.ds(qi, 1), :]
        l_sc[:] = l_in_ref[0, pl.ds(qi, 1), :]

    q_start = qi * q_block
    k_start = ki * k_block
    needed = (k_off + k_start <= q_off + q_start + q_block - 1) if causal else True

    @pl.when(needed)
    def _step():
        m_sc[:], l_sc[:], acc_sc[:] = _attend_block(
            q_ref, k_ref, v_ref, (m_sc[:], l_sc[:], acc_sc[:]),
            q_lo=q_off + q_start, k_lo=k_off + k_start,
            valid=_valid_keys(lk, k_start, k_block), sub=sub, causal=causal,
            scale=scale, guard_empty_rows=True,
            groups=_walk_groups(k_block // sub))

    @pl.when(ki == n_k - 1)
    def _finish():
        acc_out_ref[0] = acc_sc[:].T
        m_out_ref[0, pl.ds(qi, 1), :] = m_sc[:]
        l_out_ref[0, pl.ds(qi, 1), :] = l_sc[:]


def flash_attention_with_carry(q, k, v, carry=None, *, causal: bool = True,
                               q_offset=0, k_offset=0,
                               q_block: int = DEFAULT_Q_BLOCK,
                               k_block: int = DEFAULT_K_BLOCK,
                               interpret=None):
    """Pallas ring-attention local step: (acc, m, l) carry in/out.

    Same carry layout as :func:`blockwise_attention_with_carry` — acc
    [B, H, Lq, D] f32 unnormalized, m/l [B, H, Lq] f32 — so ring attention can
    use either implementation interchangeably; normalize with
    ``blockwise_attention.finalize``. ``q_offset``/``k_offset`` may be traced
    (ring step indices); they enter the kernel as SMEM scalars.
    """
    b, lq, h, d = q.shape
    lk = k.shape[1]
    scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = _use_interpret()

    qf = q.transpose(0, 2, 1, 3).reshape(b * h, lq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, lk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, lk, d)

    bq = min(q_block, lq)
    n_q = pl.cdiv(lq, bq)
    q_pad = n_q * bq - lq
    if q_pad:
        qf = jnp.pad(qf, ((0, 0), (0, q_pad), (0, 0)))
    bk = min(k_block, lk)
    n_k = pl.cdiv(lk, bk)
    if n_k * bk - lk:
        kf = jnp.pad(kf, ((0, 0), (0, n_k * bk - lk), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, n_k * bk - lk), (0, 0)))

    if carry is None:
        acc0 = jnp.zeros((b * h, n_q * bq, d), jnp.float32)
        m0 = jnp.full((b * h, n_q, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b * h, n_q, bq), jnp.float32)
    else:
        acc_c, m_c, l_c = carry
        acc0 = acc_c.reshape(b * h, lq, d).astype(jnp.float32)
        m0 = m_c.reshape(b * h, lq).astype(jnp.float32)
        l0 = l_c.reshape(b * h, lq).astype(jnp.float32)
        if q_pad:
            acc0 = jnp.pad(acc0, ((0, 0), (0, q_pad), (0, 0)))
            m0 = jnp.pad(m0, ((0, 0), (0, q_pad)), constant_values=NEG_INF)
            l0 = jnp.pad(l0, ((0, 0), (0, q_pad)))
        m0 = m0.reshape(b * h, n_q, bq)
        l0 = l0.reshape(b * h, n_q, bq)

    offs = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(k_offset, jnp.int32)])
    kernel = functools.partial(_flash_carry_kernel, lk=lk, q_block=bq, k_block=bk,
                               sub=_sub_tile(bk), causal=causal, scale=scale)
    row_plane = pl.BlockSpec((1, n_q, bq), lambda bh, i, j: (bh, 0, 0))
    acc, m, l = named_pallas_call(
        "flash_carry", kernel,
        grid=(b * h, n_q, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            row_plane,
            row_plane,
        ],
        out_specs=(
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            row_plane,
            row_plane,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b * h, n_q * bq, d), jnp.float32),
            jax.ShapeDtypeStruct((b * h, n_q, bq), jnp.float32),
            jax.ShapeDtypeStruct((b * h, n_q, bq), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((d, bq), jnp.float32),
            pltpu.VMEM((1, bq), jnp.float32),
            pltpu.VMEM((1, bq), jnp.float32),
        ],
        interpret=interpret,
    )(offs, qf, kf, vf, acc0, m0, l0)

    acc = acc[:, :lq, :].reshape(b, h, lq, d)
    m = m.reshape(b * h, n_q * bq)[:, :lq].reshape(b, h, lq)
    l = l.reshape(b * h, n_q * bq)[:, :lq].reshape(b, h, lq)
    return acc, m, l


def _use_interpret() -> bool:
    """Interpret the kernels on the CPU backend (the test mesh), compile them
    on TPU. Any other backend is an error: interpreting there would run the
    hot path orders of magnitude slower and still look like a pass."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"pallas kernels compile for 'tpu' and are interpreted on 'cpu'; "
        f"the default backend is {backend!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, k_shared, sink, causal, q_block, k_block, window, heads):
    out, _ = _flash_forward(q, k, v, causal, q_block, k_block, _use_interpret(),
                            window, k_shared, heads=heads, sink=sink)
    return out


def _flash_fwd(q, k, v, k_shared, sink, causal, q_block, k_block, window, heads):
    # Named so that a caller's ``jax.checkpoint`` whose policy lists
    # ``KEPT_NAME`` keeps them and does not launch the forward kernel again
    # for its backward; the identity, lowered to nothing, anywhere else.
    out, lse = _flash_forward(
        q, k, v, causal, q_block, k_block, _use_interpret(), window, k_shared,
        kept=lambda x: checkpoint_name(x, KEPT_NAME), heads=heads, sink=sink)
    return out, (q, k, v, k_shared, sink, out, lse)


def _flash_bwd(causal, q_block, k_block, window, heads, residuals, g):
    q, k, v, k_shared, sink, o, lse = residuals
    dq, dk, dv, *rest = _flash_backward(
        q, k, v, o, lse, g, causal, q_block, k_block, _use_interpret(),
        window=window, k_shared=k_shared, heads=heads, sink=sink)
    # the shared key columns' and the sinks' gradients, where there are such
    optional = [rest.pop(0) if x is not None else None for x in (k_shared, sink)]
    return (dq, dk, dv, *optional)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: Optional[jax.Array], *,
                    causal: bool = True, window: Optional[int] = None,
                    k_shared: Optional[jax.Array] = None,
                    heads: Optional[tuple] = None,
                    q_block: Optional[int] = None,
                    k_block: Optional[int] = None,
                    sink: Optional[jax.Array] = None) -> jax.Array:
    """Flash attention over [B, L, H, D] tensors (pallas forward and backward).

    ``v`` may be of another width than ``q`` and ``k`` (the result is ``v``'s
    wide, the scale ``1 / sqrt(D)`` of the key width). ``k_shared`` ``[B, Lk,
    Ds]``: the trailing ``Ds`` columns of every head's key where all heads
    share them (latent attention's one rotary key head); ``k`` then holds the
    leading ``D - Ds`` columns a head, nothing is repeated in memory, the
    score tile is the sum of two products, and the gradient with respect to
    ``k_shared`` is the sum over the heads. ``v`` None: ``k`` is ``[B, Lk,
    H_kv, D_k + D_v]``, a head's keys and then its values as ONE projection
    wrote them (latent attention's ``kv_up``), and its gradient comes back
    in the same form.

    Where the operands lie. Any of q, k, v may be handed as ``[B, L, heads *
    D]``, THREE dimensions: a projection's own rows, with ``heads = (H,
    H_kv)`` to say how many heads they hold. The kernels then read head
    ``n`` where it lies, column block ``n`` of those rows, through their
    index maps, and write the operand's gradient the same way, wherever ``D``
    is whole 128-lane tiles and no row of the call is padded to a block
    (:func:`_stays`; the packed ``k`` too, at two equal widths); the result
    comes back as rows, ``[B, Lq, H * D_v]``, where ``v`` was handed so (the
    packed ``k`` where ``v`` is None). A ``[B, L, heads, D]`` operand, and
    one of any other width (64, 192), is transposed to ``[B * heads, L, D]``
    by XLA around the kernels as all were before PR 41;
    ``flash.fwd.operands_relaid`` / ``flash.bwd.operands_relaid`` count those
    (0 where every operand stays, 4 / 7 where none does). Hand as rows what
    goes from a projection into this call, or from it into one, untouched;
    what is turned in between (a rotary embedding, a norm over the head
    width) is better left ``[B, L, heads, D]``: XLA computes it in the
    transposed layout for nothing, and copies float32 arrays across to get
    from one layout to the other.

    ``window=W`` (causal only): the query at ``i`` sees the keys ``i - W < j
    <= i``, itself and the ``W - 1`` before it. Tiles wholly below the band
    are neither computed nor, where they are a grid step, copied; tiles either
    edge crosses run the masked body. A window narrower than a key tile (``W <
    512`` at the default blocks, lengths of whole lane tiles) runs the walk
    fitted to the band instead: each 128-query chunk of a q block against the
    one tile of ``128 * (ceil((W - 1) / 128) + 1)`` keys that ends with its
    own (256 at ``W = 128``, half of them visible where two 512 x 512 tiles
    were an eighth full), and the one-pass backward each 128-key chunk
    against as many queries from its own on; the two-kernel backward (past
    ``_RESIDENT_DQ_BYTES``) and explicit blocks that are no whole lane tiles
    keep the tiles' own walk. ``k`` / ``v`` may hold fewer heads than
    ``q``, ``H_kv`` dividing ``H`` (grouped KV heads): query head ``n`` reads
    KV head ``n // (H / H_kv)`` through the kernels' index maps, nothing is
    repeated in memory, and dK / dV are the sum over a group's query heads.

    ``sink`` ``[H]`` (float32; learned): query head ``n``'s softmax has one
    more logit, ``sink[n]``, in its denominator, which carries no value:
    ``p_ij = exp(s_ij) / (exp(sink_n) + sum_j' exp(s_ij'))``. It is a logit
    as it is (``1 / sqrt(D)`` is the keys'), the online softmax starts from
    ``(m, l, acc) = (sink_n, 1, 0)``, the saved log-sum-exp includes it, the
    backward's ``dS = p (dP - D)`` stands, and ``d sink_n = - sum_i
    exp(sink_n - lse_i) D_i`` is computed beside the kernels. Such a call's
    kernels carry device names of their own (``flash_sink_fwd``,
    ``flash_sink_bwd_dkv``, ``flash_sink_bwd_dq``); a call without one traces
    and lowers what it did before there were sinks.

    ``q_block`` / ``k_block`` left at None: the forward and the backward pick
    their blocks from the shape (:func:`_forward_blocks`,
    :func:`_backward_blocks`), and the backward its schedule: one pass while
    the float32 dQ of a (batch, head) fits ``_RESIDENT_DQ_BYTES``.

    Under a mesh of several devices the kernels run per device on its share
    of the batch (:func:`autodist_tpu.parallel.mesh.per_device`)."""
    from autodist_tpu.parallel.mesh import per_device
    if window is not None and (not causal or window < 1):
        raise ValueError(f"window={window!r} needs causal=True and window >= 1")
    if heads is None and any(_given_as_rows(q, k, v)):
        raise ValueError("heads=(H, H_kv) must say how many heads an operand "
                         "of three dimensions holds")
    h, h_kv = heads or (q.shape[2], k.shape[2])
    _kv_group(_as_heads(q, h), _as_heads(k, h_kv))
    _shared_cols(_as_heads(q, h), _as_heads(k, h_kv), k_shared, packed=v is None)
    if sink is not None and sink.shape != (h,):
        raise ValueError(f"sink is {sink.shape}, one logit a query head is ({h},)")
    given = [x is not None for x in (q, k, v, k_shared, sink)]

    def call(*operands):
        operands = iter(operands)
        q, k, v, ks, sinks = (next(operands) if there else None
                              for there in given)
        return _flash(q, k, v, ks, sinks, causal, q_block, k_block, window, heads)

    operands = [x for x in (q, k, v, k_shared, sink) if x is not None]
    # the sinks are the heads', whole on every device
    return per_device(call, operands, batched=tuple(
        x is not sink for x in operands))


def band_pairs(lq: int, lk: int, causal: bool = True, window=None,
               d: int = 128, itemsize: int = 2) -> tuple:
    """``(visible, computed)`` (query, key) pairs of one (batch, head) of a
    forward call under zero offsets: the pairs the mask keeps, and the pairs
    of the score tiles the walk runs (plain and masked), at the blocks
    :func:`_forward_blocks` picks for keys ``d`` wide. Their ratio is how
    full the computed tiles are: a window of 128 fills an eighth of the two
    512 x 512 tiles a q block it would touch, and half of the fitted walk's
    four ``[256, 128]`` (:func:`_band_span`)."""
    bq, bk, sub = _forward_blocks(lq, lk, d, itemsize, None, None)
    plain, masked, _ = _count_tiles(lq, lk, bq, bk, sub, causal, window)
    i = np.arange(lq) + (lk - lq)           # a query's own position
    last = np.minimum(i, lk - 1) if causal else np.full(lq, lk - 1)
    first = np.zeros(lq, int) if window is None else np.maximum(i - window + 1, 0)
    visible = int(np.clip(last - first + 1, 0, None).sum())
    span = _band_span(window, bq, bk, sub)
    return visible, (plain + masked) * (_LANES * span if span else bq * sub)
