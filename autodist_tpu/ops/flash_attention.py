"""Flash attention — pallas TPU kernels, forward AND backward.

Forward: grid (batch*heads, q-blocks, k-blocks); each K/V block streams through
VMEM via its own BlockSpec while VMEM scratch carries the online-softmax state
(running max, denominator, unnormalized accumulator) across the k dimension of the
grid — the [L, L] score matrix never exists, and resident VMEM is O(q_block +
k_block), independent of sequence length. Causal upper-triangular blocks are
skipped entirely (~2x fewer FLOPs). The per-row logsumexp is emitted as a residual
for the backward pass.

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

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.ops.blockwise_attention import NEG_INF
from autodist_tpu.ops.named_call import named_pallas_call

# 512-blocks amortize grid/DMA overhead into MXU-sized matmuls: measured on a TPU
# v5e chip (B=8 H=8 D=64, causal, fwd+bwd) flash@512 beats XLA's fused dot-product
# attention at L>=2048 (10.1 vs 10.9 ms) and 1.5x at L=4096 (21.7 vs 32.5 ms),
# while 128-blocks were 2.5x SLOWER than XLA. 1024 is faster still (16 ms at
# L=4096) at higher VMEM pressure — worth passing explicitly for long context.
DEFAULT_Q_BLOCK = 512
DEFAULT_K_BLOCK = 512
_LANES = 128  # scratch minor dim (TPU lane count)


def _online_softmax_step(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                         q_start, k_start, q_off, k_off, lk, causal, scale):
    """One k-block online-softmax update against the VMEM-resident (acc, m, l)
    state — the single definition shared by the plain forward kernel and the
    carry variant. Matmul operands stay in the input dtype (bf16 runs the MXU at
    full rate); accumulation and softmax arithmetic are f32."""
    q = q_ref[0]                                      # [bq, d]
    k_blk = k_ref[0]                                  # [bk, d]
    v_blk = v_ref[0]
    bq, bk = q.shape[0], k_blk.shape[0]
    scores = scale * jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)           # [bq, bk]
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    invalid = k_pos >= lk                             # tail padding (local)
    if causal:
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        invalid = invalid | (k_off + k_pos > q_off + q_pos)
    scores = jnp.where(invalid, NEG_INF, scores)

    m_prev = m_ref[:, :1]                             # [bq, 1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
    correction = jnp.exp(m_prev - m_new)
    p = jnp.where(scores <= NEG_INF * 0.5, 0.0, jnp.exp(scores - m_new))
    l_ref[:] = jnp.broadcast_to(l_prev * correction + p.sum(axis=-1, keepdims=True),
                                l_ref.shape)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    acc_ref[:] = acc_ref[:] * correction + jax.lax.dot_general(
        p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _flash_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                  l_ref, *,
                  lk: int, q_block: int, k_block: int, causal: bool, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)
    # Global offsets of the first local query/key (SMEM scalars): ring attention
    # passes the ring-shifted key offset so causal masking stays globally correct;
    # the plain path passes zeros.
    q_off = off_ref[0]
    k_off = off_ref[1]

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = qi * q_block
    k_start = ki * k_block
    # Causal: skip blocks strictly above the (global) diagonal.
    needed = (k_off + k_start <= q_off + q_start + q_block - 1) if causal else True

    @pl.when(needed)
    def _step():
        _online_softmax_step(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                             q_start, k_start, q_off, k_off, lk, causal, scale)

    @pl.when(ki == n_k - 1)
    def _finish():
        l_fin = l_ref[:, :1]
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)
        # Per-row logsumexp residual for the backward pass. Padding query rows get
        # a finite lse too (zero-padded q still attends real keys); the backward is
        # safe for them ONLY because dO is zero-padded there — do not rely on lse
        # being NEG_INF for masked rows. Layout: [bh, n_q, bq] with the whole
        # (n_q, bq) plane as one resident block (TPU tiling forbids a [1, bq]
        # block); each q-block writes its row.
        lse = m_ref[:, 0] + jnp.log(jnp.maximum(l_ref[:, 0], 1e-30))
        lse_ref[0, qi, :] = lse


def _flash_forward(q, k, v, causal: bool, q_block: int, k_block: int,
                   interpret: bool):
    """Returns (out [B, Lq, H, D], lse [B*H, n_q, bq] f32)."""
    b, lq, h, d = q.shape
    lk = k.shape[1]
    scale = 1.0 / (d ** 0.5)

    # Collapse (batch, head) into the grid's first axis: [B*H, L, D].
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, lq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, lk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, lk, d)

    bq = min(q_block, lq)
    n_q = pl.cdiv(lq, bq)
    if n_q * bq - lq:
        qf = jnp.pad(qf, ((0, 0), (0, n_q * bq - lq), (0, 0)))
    bk = min(k_block, lk)
    n_k = pl.cdiv(lk, bk)
    if n_k * bk - lk:
        kf = jnp.pad(kf, ((0, 0), (0, n_k * bk - lk), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, n_k * bk - lk), (0, 0)))

    kernel = functools.partial(_flash_kernel, lk=lk, q_block=bq, k_block=bk,
                               causal=causal, scale=scale)
    offs = jnp.zeros((2,), jnp.int32)
    out, lse = named_pallas_call(
        "flash_fwd", kernel,
        grid=(b * h, n_q, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
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
            pltpu.VMEM((bq, d), jnp.float32),       # acc
            pltpu.VMEM((bq, _LANES), jnp.float32),  # running max
            pltpu.VMEM((bq, _LANES), jnp.float32),  # running denominator
        ],
        interpret=interpret,
    )(offs, qf, kf, vf)

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
                        lk: int, q_block: int, k_block: int, causal: bool,
                        scale: float):
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
        acc_sc[:] = acc_in_ref[0]
        m_sc[:] = jnp.broadcast_to(m_in_ref[0, qi, :][:, None], m_sc.shape)
        l_sc[:] = jnp.broadcast_to(l_in_ref[0, qi, :][:, None], l_sc.shape)

    q_start = qi * q_block
    k_start = ki * k_block
    needed = (k_off + k_start <= q_off + q_start + q_block - 1) if causal else True

    @pl.when(needed)
    def _step():
        _online_softmax_step(q_ref, k_ref, v_ref, acc_sc, m_sc, l_sc,
                             q_start, k_start, q_off, k_off, lk, causal, scale)

    @pl.when(ki == n_k - 1)
    def _finish():
        acc_out_ref[0] = acc_sc[:]
        m_out_ref[0, qi, :] = m_sc[:, 0]
        l_out_ref[0, qi, :] = l_sc[:, 0]


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
                               causal=causal, scale=scale)
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
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
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
    return _flash_backward(q, k, v, o, lse, g, causal, q_block, k_block,
                           _use_interpret())


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, q_block: int = DEFAULT_Q_BLOCK,
                    k_block: int = DEFAULT_K_BLOCK) -> jax.Array:
    """Flash attention over [B, L, H, D] tensors (pallas forward and backward).

    Under a mesh of several devices the kernels run per device on its share
    of the batch (:func:`autodist_tpu.parallel.mesh.per_device`)."""
    from autodist_tpu.parallel.mesh import per_device
    return per_device(
        lambda q, k, v: _flash(q, k, v, causal, q_block, k_block),
        (q, k, v), batched=(True, True, True))
