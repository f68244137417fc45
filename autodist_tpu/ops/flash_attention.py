"""Flash attention — pallas TPU kernels, forward AND backward.

Forward: grid (batch*heads, q-blocks, k-blocks); VMEM scratch carries the
online-softmax state (running max, denominator, unnormalized accumulator) across
the k dimension of the grid — the [L, L] score matrix never exists. While K and V
of one (batch, head) are small they are ONE resident block (one grid step a q
block, nothing copied for a block above the diagonal); longer ones stream in
blocks, and a block above the diagonal is neither copied nor computed. Inside a
grid step the resident block is walked in key tiles whose score tile is held
TRANSPOSED, [keys, queries]: the softmax statistics reduce along sublanes and
are lane-dense [1, q_block] vectors. Each tile runs the body of its class —
plain (below the diagonal, no padded key: no iota, no compare, no select),
masked (crossed by the diagonal or holding the ragged tail) or skipped — decided
from grid indices and the SMEM offsets, so ring attention's traced offsets
classify at run time. The per-row logsumexp is emitted as a residual for the
backward pass.

Backward (FlashAttention-2 style): scores are recomputed blockwise from the saved
logsumexp, so nothing quadratic is ever materialized. Two kernels:

- dK/dV: grid (batch*heads, k-blocks, q-blocks) — each k block accumulates
  p^T dO and ds^T q across all its query blocks in VMEM scratch.
- dQ:    grid (batch*heads, q-blocks, k-blocks) — each q block accumulates
  ds k across its key blocks.

The row term D_i = rowsum(dO * O) is precomputed in XLA (elementwise, fused).

On non-TPU backends the kernels run in pallas interpret mode, so tests exercise
the same code path on the CPU-sim mesh.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
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
# Non-causal L 1,024: 0.554 ms (1.406). The backward still runs 512 x 512 row-major.
DEFAULT_Q_BLOCK = 512
DEFAULT_K_BLOCK = 512
_KEY_TILE = 512             # keys a score tile of the forward: [512, bq] f32
_RESIDENT_KV_BYTES = 1 << 20     # K (or V) of one (batch, head) kept in VMEM
_STREAM_K_BLOCK = 2048           # K/V rows a grid step beyond that


def _sub_tile(bk: int) -> int:
    """Keys a tile: the widest of 512/256/128 that divides the K/V block, the
    block itself where none does (a ragged or tiny block is one tile)."""
    for sub in (_KEY_TILE, 256, 128):
        if bk % sub == 0:
            return sub
    return bk


def _is_static(n) -> bool:
    return isinstance(n, (int, np.integer))


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


def _attend_block(q_ref, k_ref, v_ref, state, *, q_lo, k_lo, valid, sub: int,
                  causal: bool, scale: float, guard_empty_rows: bool):
    """Online-softmax update of ``state = (m [1, bq], l [1, bq], acc [d, bq])``
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
    a carry that starts at NEG_INF), so a masked score must not read as
    ``exp(NEG_INF - NEG_INF) = 1``. With zero offsets every query sees key 0
    in its first tile and the guard is dead."""
    q = q_ref[0]                                      # [bq, d]
    bq, bk = q.shape[0], k_ref.shape[1]
    # scale once per q block where that is exact (a power of two, as at
    # d = 16, 64, 256), else on the score tile as before.
    prescale = math.frexp(scale)[0] == 0.5
    if prescale:
        q = q * jnp.asarray(scale, q.dtype)
    n_plain, n_need = _tile_counts(q_lo, k_lo, valid, bq, bk, sub, causal)

    def tile(j, state, masked: bool):
        m_prev, l_prev, acc = state
        if sub == bk:
            start = 0                                 # the one tile, whatever j
        else:
            start = j * sub
            if not _is_static(start):
                start = pl.multiple_of(start, sub)
        k_t = k_ref[0, pl.ds(start, sub), :]          # [sub, d]
        v_t = v_ref[0, pl.ds(start, sub), :]
        scores = jax.lax.dot_general(
            k_t, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [sub, bq]
        if not prescale:
            scores = scale * scores
        if masked:
            key = jax.lax.broadcasted_iota(jnp.int32, (sub, bq), 0)
            invalid = None
            if valid is not None:
                invalid = key >= valid - start
            if causal:
                query = jax.lax.broadcasted_iota(jnp.int32, (sub, bq), 1)
                above = key - query > q_lo - k_lo - start
                invalid = above if invalid is None else invalid | above
            scores = jnp.where(invalid, NEG_INF, scores)
        m_new = jnp.maximum(m_prev, scores.max(axis=0, keepdims=True))
        correction = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        if masked and guard_empty_rows:
            p = jnp.where(scores <= NEG_INF * 0.5, 0.0, p)
        l_new = l_prev * correction + p.sum(axis=0, keepdims=True)
        acc = acc * correction + jax.lax.dot_general(
            v_t, p.astype(v_t.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [d, bq]
        return m_new, l_new, acc

    state = _loop(0, n_plain, lambda j, s: tile(j, s, False), state)
    return _loop(n_plain, n_need, lambda j, s: tile(j, s, True), state)


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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                  lk: int, sub: int, causal: bool, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = qi * bq
    k_start = ki * bk
    # Causal: skip K/V blocks strictly above the diagonal.
    needed = (k_start <= q_start + bq - 1) if causal else True

    @pl.when(needed)
    def _step():
        m_ref[:], l_ref[:], acc_ref[:] = _attend_block(
            q_ref, k_ref, v_ref, (m_ref[:], l_ref[:], acc_ref[:]),
            q_lo=q_start, k_lo=k_start, valid=_valid_keys(lk, k_start, bk),
            sub=sub, causal=causal, scale=scale, guard_empty_rows=False)

    @pl.when(ki == n_k - 1)
    def _finish():
        l_fin = jnp.maximum(l_ref[:], 1e-30)                      # [1, bq]
        o_ref[0] = (acc_ref[:] / l_fin).T.astype(o_ref.dtype)     # [bq, d]
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
    step and no copy for a block above the diagonal — rounded up to whole key
    tiles (the tail is padding, masked like any ragged tail)."""
    bq = min(q_block or DEFAULT_Q_BLOCK, lq)
    if k_block is None:
        bk = lk if lk <= _KEY_TILE else pl.cdiv(lk, _KEY_TILE) * _KEY_TILE
        if bk * d * itemsize > _RESIDENT_KV_BYTES:
            bk = _STREAM_K_BLOCK
    else:
        bk = min(k_block, lk)
    return bq, bk, _sub_tile(bk)


def _count_tiles(lq: int, lk: int, bq: int, bk: int, sub: int, causal: bool):
    """(plain, masked, skipped) score tiles of one (batch, head) under zero
    offsets, at the granularity the body runs them: [bq, sub]."""
    n_q, n_k = pl.cdiv(lq, bq), pl.cdiv(lk, bk)
    plain = need = 0
    for qi in range(n_q):
        for ki in range(n_k):
            a, b = _tile_counts(qi * bq, ki * bk, _valid_keys(lk, ki * bk, bk),
                                bq, bk, sub, causal)
            plain, need = plain + int(a), need + int(b)
    return plain, need - plain, n_q * n_k * (bk // sub) - need


def _flash_forward(q, k, v, causal: bool, q_block, k_block, interpret: bool):
    """Returns (out [B, Lq, H, D], lse [B*H, n_q, bq] f32)."""
    b, lq, h, d = q.shape
    lk = k.shape[1]
    scale = 1.0 / (d ** 0.5)

    # Collapse (batch, head) into the grid's first axis: [B*H, L, D].
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, lq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, lk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, lk, d)

    bq, bk, sub = _forward_blocks(lq, lk, d, q.dtype.itemsize, q_block, k_block)
    n_q = pl.cdiv(lq, bq)
    if n_q * bq - lq:
        qf = jnp.pad(qf, ((0, 0), (0, n_q * bq - lq), (0, 0)))
    n_k = pl.cdiv(lk, bk)
    if n_k * bk - lk:
        kf = jnp.pad(kf, ((0, 0), (0, n_k * bk - lk), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, n_k * bk - lk), (0, 0)))

    plain, masked, skipped = _count_tiles(lq, lk, bq, bk, sub, causal)
    telemetry.gauge("flash.fwd.tiles_plain").set(plain)
    telemetry.gauge("flash.fwd.tiles_masked").set(masked)
    telemetry.gauge("flash.fwd.tiles_skipped").set(skipped)

    kernel = functools.partial(_flash_kernel, lk=lk, sub=sub, causal=causal,
                               scale=scale)
    if causal and n_k > 1:
        # A K/V block above the diagonal names the last one below it again, so
        # its (skipped) grid step copies nothing in.
        def kv_index(bh, i, j):
            return bh, jnp.minimum(j, ((i + 1) * bq - 1) // bk), 0
    else:
        def kv_index(bh, i, j):
            return bh, j, 0
    out, lse = named_pallas_call(
        "flash_fwd", kernel,
        grid=(b * h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=(
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
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
            jax.ShapeDtypeStruct((b * h, n_q * bq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, n_q, bq), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((d, bq), jnp.float32),   # acc, transposed
            pltpu.VMEM((1, bq), jnp.float32),   # running max
            pltpu.VMEM((1, bq), jnp.float32),   # running denominator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)

    out = out[:, :lq, :].reshape(b, h, lq, d).transpose(0, 2, 1, 3)
    return out, lse


def _recompute_p_ds(q, do, k_blk, v_blk, lse, dd, q_start, k_start, lk, causal,
                    scale, q_off=0, k_off=0):
    """Shared backward block math: p [bq, bk] and ds (pre-scale) from a recomputed
    score block. Matmul operands keep the input dtype (MXU rate); p/ds are f32."""
    bq, bk = q.shape[0], k_blk.shape[0]
    scores = scale * jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    invalid = k_pos >= lk
    if causal:
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        invalid = invalid | (k_off + k_pos > q_off + q_pos)
    p = jnp.where(invalid, 0.0, jnp.exp(scores - lse))            # [bq, bk]
    dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [bq, bk]
    ds = p * (dp - dd)
    return p, ds


def _flash_bwd_dkdv_kernel(off_ref, q_ref, do_ref, lse_ref, dd_ref, k_ref, v_ref,
                           dk_ref, dv_ref, dk_acc, dv_acc, *,
                           lk: int, q_block: int, k_block: int, causal: bool,
                           scale: float):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    n_q = pl.num_programs(2)
    q_off = off_ref[0]
    k_off = off_ref[1]

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = qi * q_block
    k_start = ki * k_block
    needed = (k_off + k_start <= q_off + q_start + q_block - 1) if causal else True

    @pl.when(needed)
    def _step():
        q = q_ref[0]
        do = do_ref[0]
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        lse = lse_ref[0, qi, :][:, None]                  # [bq, 1]
        dd = dd_ref[0, qi, :][:, None]
        p, ds = _recompute_p_ds(q, do, k_blk, v_blk, lse, dd, q_start, k_start,
                                lk, causal, scale, q_off, k_off)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[:] += scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(off_ref, q_ref, do_ref, lse_ref, dd_ref, k_ref, v_ref,
                         dq_ref, dq_acc, *,
                         lk: int, q_block: int, k_block: int, causal: bool,
                         scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)
    q_off = off_ref[0]
    k_off = off_ref[1]

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = qi * q_block
    k_start = ki * k_block
    needed = (k_off + k_start <= q_off + q_start + q_block - 1) if causal else True

    @pl.when(needed)
    def _step():
        q = q_ref[0]
        do = do_ref[0]
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        lse = lse_ref[0, qi, :][:, None]
        dd = dd_ref[0, qi, :][:, None]
        _, ds = _recompute_p_ds(q, do, k_blk, v_blk, lse, dd, q_start, k_start,
                                lk, causal, scale, q_off, k_off)
        dq_acc[:] += scale * jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def prepare_backward_q_side(q, o, g, q_block):
    """Query-side backward layout: transposed/padded q and dO plus the row term
    D_i = rowsum(dO * O) in the kernels' [bh, n_q, bq] plane layout. Depends only
    on the query side, so ring attention computes it ONCE and reuses it across
    every ring step."""
    b, lq, h, d = q.shape
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, lq, d)
    dof = g.transpose(0, 2, 1, 3).reshape(b * h, lq, d)
    of = o.transpose(0, 2, 1, 3).reshape(b * h, lq, d)
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
                       out_dtype=None):
    """Backward against one K/V shard from prepared query-side layout. Returns
    (dq, dk, dv) in [B, L, H, D]; ``out_dtype`` overrides the kernels' output
    dtype (ring passes f32 so per-step contributions accumulate unquantized)."""
    b, lq, h, d = q_shape
    lk = k.shape[1]
    scale = 1.0 / (d ** 0.5)
    offs = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(k_offset, jnp.int32)])

    kf = k.transpose(0, 2, 1, 3).reshape(b * h, lk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, lk, d)
    bk = min(k_block, lk)
    n_k = pl.cdiv(lk, bk)
    k_pad = n_k * bk - lk
    if k_pad:
        kf = jnp.pad(kf, ((0, 0), (0, k_pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, k_pad), (0, 0)))
    dq_dtype = out_dtype or qf.dtype
    dk_dtype = out_dtype or k.dtype
    dv_dtype = out_dtype or v.dtype

    q_spec = pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, j, 0))
    row_spec = pl.BlockSpec((1, n_q, bq), lambda bh, i, j: (bh, 0, 0))
    kv_spec = pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, i, 0))

    dkdv_kernel = functools.partial(
        _flash_bwd_dkdv_kernel, lk=lk, q_block=bq, k_block=bk, causal=causal,
        scale=scale)
    dk, dv = named_pallas_call(
        "flash_bwd_dkv", dkdv_kernel,
        grid=(b * h, n_k, n_q),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  q_spec, q_spec, row_spec, row_spec, kv_spec, kv_spec],
        out_specs=(
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b * h, n_k * bk, d), dk_dtype),
            jax.ShapeDtypeStruct((b * h, n_k * bk, d), dv_dtype),
        ),
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
    )(offs, qf, dof, lse, dd, kf, vf)

    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, lk=lk, q_block=bq, k_block=bk, causal=causal,
        scale=scale)
    dq = named_pallas_call(
        "flash_bwd_dq", dq_kernel,
        grid=(b * h, n_q, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, n_q, bq), lambda bh, i, j: (bh, 0, 0)),
            pl.BlockSpec((1, n_q, bq), lambda bh, i, j: (bh, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, n_q * bq, d), dq_dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(offs, qf, dof, lse, dd, kf, vf)

    dq = dq[:, :lq, :].reshape(b, h, lq, d).transpose(0, 2, 1, 3)
    dk = dk[:, :lk, :].reshape(b, h, lk, d).transpose(0, 2, 1, 3)
    dv = dv[:, :lk, :].reshape(b, h, lk, d).transpose(0, 2, 1, 3)
    return dq, dk, dv


def _flash_backward(q, k, v, o, lse, g, causal, q_block, k_block, interpret,
                    q_offset=0, k_offset=0, out_dtype=None):
    qf, dof, dd, bq, n_q = prepare_backward_q_side(q, o, g, q_block)
    return _flash_backward_kv(qf, dof, lse, dd, k, v, causal, bq, n_q, k_block,
                              interpret, q.shape, q_offset=q_offset,
                              k_offset=k_offset, out_dtype=out_dtype)


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
            scale=scale, guard_empty_rows=True)

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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, q_block, k_block):
    out, _ = _flash_forward(q, k, v, causal, q_block, k_block, _use_interpret())
    return out


def _flash_fwd(q, k, v, causal, q_block, k_block):
    out, lse = _flash_forward(q, k, v, causal, q_block, k_block, _use_interpret())
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, q_block, k_block, residuals, g):
    q, k, v, o, lse = residuals
    return _flash_backward(q, k, v, o, lse, g, causal,
                           q_block or DEFAULT_Q_BLOCK, k_block or DEFAULT_K_BLOCK,
                           _use_interpret())


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, q_block: Optional[int] = None,
                    k_block: Optional[int] = None) -> jax.Array:
    """Flash attention over [B, L, H, D] tensors (pallas forward and backward).

    ``q_block`` / ``k_block`` left at None: the forward picks its blocks from
    the shape (:func:`_forward_blocks`), the backward runs at
    ``DEFAULT_Q_BLOCK`` x ``DEFAULT_K_BLOCK``.

    Under a mesh of several devices the kernels run per device on its share
    of the batch (:func:`autodist_tpu.parallel.mesh.per_device`)."""
    from autodist_tpu.parallel.mesh import per_device
    return per_device(
        lambda q, k, v: _flash(q, k, v, causal, q_block, k_block),
        (q, k, v), batched=(True, True, True))
