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

Three kernels:
- forward: grid (n-blocks, v-blocks); VMEM scratch carries (m, l) across the v
  dimension; last v-block writes ``lse = m + log l``.
- d(h):    grid (n-blocks, v-blocks); accumulates g*p @ w^T tiles in VMEM.
- d(w,b):  grid (v-blocks, n-blocks); accumulates h^T @ g*p and column-sums.

Measured on a v5e chip: in the full flagship training step the fused head is
faster than the XLA head at equal batch (410k vs 398k tokens/s at bs 256) and
— because nothing here scales with N*V — unlocks batch sizes whose logits
cannot exist: bs 384 (~428k tokens/s, the flagship bench config) OOMs with a
materialized head. Larger still: V=262k (32 GiB of logits) and N=262k
(16 GiB) both train where XLA OOMs, and the lm1b example trains its exact
793,471-word vocabulary with the TRUE softmax objective (48 GiB of logits if
materialized; the reference needed sampled softmax) at ~17k words/s/chip end
to end (bs 96, Adafactor — Adam's unfactored moments on the 4.9 GiB of
tables exceed one chip's HBM).
(An isolated loss+grads microbench is near-parity — 73 vs 69 ms —
because the two backward logit recomputes cost roughly what the avoided HBM
traffic saves; inside the full step, overlap with the rest of the model tips
it to a win.)

On the CPU backend the kernels run in pallas interpret mode, so the test mesh
exercises the same code path; ``tests/test_chip_compile.py`` compiles them
for a described v5e. The chip figures above are from round 5.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.ops.blockwise_attention import NEG_INF
from autodist_tpu.ops.flash_attention import _use_interpret
from autodist_tpu.ops.named_call import named_pallas_call

_LANES = 128
DEFAULT_N_BLOCK = 512
DEFAULT_V_BLOCK = 1024
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


# Mosaic refuses a kernel whose scoped VMEM allocation exceeds 16 MiB on v5e
# (RESOURCE_EXHAUSTED at compile time). The budget is that limit less 256 KiB
# for the spread of the temporaries model below against the compiler's own
# count (within 0.2 MiB over the grid it was fitted on).
_VMEM_BUDGET = (16 << 20) - (256 << 10)

# What Mosaic allocates beyond the pipeline buffers and scratch: values the
# kernel body materializes in VMEM (the f32 [bn, bv] logits/probability plane
# and its cast for the second matmul, masked and transposed copies of the h
# tile, the masked copy of the w tile). Bytes per element of ([bn, bv] plane,
# [bn, d] h tile, [d, bv] w tile), keyed by kernel and activation itemsize.
# Upper bounds over both table layouts and both table dtypes, fitted to the
# scoped allocations libtpu 0.0.34 reports for v5e at d in {512, 768, 1024},
# bn in {256, 512}, bv in {256, 512, 1024}; tests/test_chip_compile.py asks
# the compiler itself.
_TEMP_BYTES = {
    ("fwd", 2): (4.0, 3.0, 1.0),
    ("dh", 2): (2.5, 3.5, 2.0),
    ("dw", 2): (4.5, 4.0, 0.5),
    ("fwd", 4): (4.0, 0.5, 0.0),
    ("dh", 4): (6.5, 1.0, 0.0),
    ("dw", 4): (4.5, 1.0, 0.5),
}


def _vmem_need(kernel: str, d: int, bn: int, bv: int, h_size: int,
               w_size: int) -> float:
    """Scoped VMEM bytes one kernel ("fwd", "dh" or "dw") takes at these
    tiles: double-buffered input/output tiles, scratch accumulators, and the
    in-kernel temporaries of ``_TEMP_BYTES``. The whole-array lse/g planes
    are not in it: the compiler's count does not move with the row count."""
    h_tiles = 2 * bn * d * h_size
    w_tiles = 2 * d * bv * w_size
    if kernel == "fwd":
        # running max + denominator scratch, (bn, LANES) f32 each
        buffers = h_tiles + w_tiles + 2 * 4 * bn * _LANES
    elif kernel == "dh":
        # output [bn, d] tile + f32 [bn, d] accumulator
        buffers = h_tiles + w_tiles + 2 * bn * d * h_size + 4 * bn * d
    else:
        # dw output tile + f32 dw accumulator + the [_LANES, bv] f32 db
        # accumulator + the (1, bv) db output tile
        buffers = (h_tiles + w_tiles + 2 * d * bv * w_size + 4 * d * bv
                   + 4 * _LANES * bv + 2 * bv * w_size)
    plane, h_tile, w_tile = _TEMP_BYTES[(kernel, 2 if h_size <= 2 else 4)]
    return buffers + plane * bn * bv + h_tile * bn * d + w_tile * d * bv


def _fit_blocks(d: int, bn: int, bv: int, h_size: int, w_size: int,
                backward: bool):
    """Shrink (bn, bv) until every kernel launched with them fits the budget.

    The footprint scales with the model dim, the table dtype and the tile
    plane: a [d, bv] table tile is double-buffered on input AND (for the dw
    kernel) on output, plus an f32 accumulator, and each kernel spills a few
    bytes per [bn, bv] logit to VMEM — so the defaults that fit d=512 overflow
    at d=768 with an f32 table and at d=1024 with a bf16 one. The backward
    pass launches TWO kernels (dh and dw/db) with the same blocks, so it
    budgets against the larger. Halving clamps at one lane tile; block size
    only changes tiling, not results (beyond fp summation order).

    Vocab blocks shrink first: halving bv keeps the total table traffic and
    the row-block count (hence table passes) unchanged, while halving bn
    doubles the fwd/dh kernels' full-table re-streams — measured 15% slower
    on the 793k-vocab full-softmax when bn gives way first."""
    kernels = ("dh", "dw") if backward else ("fwd",)

    def need(bn_, bv_):
        return max(_vmem_need(k, d, bn_, bv_, h_size, w_size)
                   for k in kernels)
    while bv > _LANES and need(bn, bv) > _VMEM_BUDGET:
        bv = max(_LANES, bv // 2)
    while bn > _LANES and need(bn, bv) > _VMEM_BUDGET:
        bn = max(_LANES, bn // 2)
    if need(bn, bv) > _VMEM_BUDGET:
        # Refusing here names the cause; the compiler's RESOURCE_EXHAUSTED
        # names an allocation size and nothing the caller can change.
        raise ValueError(
            f"fused_softmax_xent: even the minimum ({bn}, {bv}) tiling "
            f"needs {need(bn, bv) / 2**20:.1f} MiB of VMEM (budget "
            f"{_VMEM_BUDGET / 2**20:.2f} MiB) at d={d} with {h_size}-byte "
            f"activations and a {w_size}-byte table; use a smaller model "
            f"dim or the XLA head (fused_head=False)")
    return bn, bv


def _w_spec(d, bv, w_vd, index2):
    """BlockSpec for one vocab tile of w in its stored layout. ``index2`` maps
    grid coords to the vocab-block index."""
    if w_vd:
        return pl.BlockSpec((bv, d), lambda *a: (index2(*a), 0))
    return pl.BlockSpec((d, bv), lambda *a: (0, index2(*a)))


def _forward(h, w, b, bn, bv, interpret, w_vd):
    bn, bv = _fit_blocks(h.shape[1], bn, bv, h.dtype.itemsize,
                         w.dtype.itemsize, backward=False)
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
        interpret=interpret,
    )(h, w, b.reshape(1, -1))
    return lse.reshape(n_n * bn)[:n]


# ------------------------------------------------------------------ backward

def _dh_kernel(h_ref, w_ref, b_ref, lse_ref, g_ref, dh_ref, acc_ref, *, n_v: int,
               w_vd: bool, bv: int, v: int):
    ni = pl.program_id(0)
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    logits, wt = _logits_tile(h_ref, w_ref, b_ref, w_vd, vi, bv, v)
    lse = lse_ref[0, ni, :]                                   # [bn]
    gp = jnp.exp(logits - lse[:, None]) * g_ref[0, ni, :][:, None]  # [bn, bv]
    dims = (((1,), (0,)), ((), ())) if w_vd else (((1,), (1,)), ((), ()))
    acc_ref[:] += jax.lax.dot_general(
        gp.astype(wt.dtype), wt, dims,
        preferred_element_type=jnp.float32)                   # [bn, d]

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

    logits, _ = _logits_tile(h_ref, w_ref, b_ref, w_vd, vi, bv, v)  # [bn, bv]
    lse = lse_ref[0, ni, :]
    gp = jnp.exp(logits - lse[:, None]) * g_ref[0, ni, :][:, None]
    # The dw/db contraction runs over the row (token) axis, so the ragged last
    # row block's undefined lanes must be hard zeros on BOTH operands: gp rows
    # (g pads to 0, but 0 * garbage-inf logits would be NaN) and h rows.
    row = ni * bn + jax.lax.broadcasted_iota(jnp.int32, gp.shape, 0)
    gp = jnp.where(row < n, gp, 0.0)
    hrow = ni * bn + jax.lax.broadcasted_iota(jnp.int32, h_ref.shape, 0)
    ht = jnp.where(hrow < n, h_ref[...], jnp.zeros((), h_ref.dtype))
    gph = gp.astype(ht.dtype)
    if w_vd:
        dw_acc[:] += jax.lax.dot_general(                     # [bv, d]
            gph, ht, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        dw_acc[:] += jax.lax.dot_general(                     # [d, bv]
            ht, gph, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    db_acc[:, :] += jnp.broadcast_to(gp.sum(axis=0)[None, :], db_acc.shape)

    @pl.when(ni == n_n - 1)
    def _finish():
        dw_ref[...] = dw_acc[:].astype(dw_ref.dtype)
        db_ref[...] = db_acc[:1, :].astype(db_ref.dtype)


def _backward(h, w, b, lse, g, bn, bv, interpret, w_vd):
    bn, bv = _fit_blocks(h.shape[1], bn, bv, h.dtype.itemsize,
                         w.dtype.itemsize, backward=True)
    n, d, v, n_n, n_v = _shapes(h, w, bn, bv, w_vd)
    bvec = b.reshape(1, -1)
    # The lse/g planes are tiny [N] vectors; padding THEM is cheap (unlike the
    # table). Padding rows must contribute nothing: gradient pads as zero AND
    # lse pads large-positive so exp underflows (see _PAD_LSE).
    lse_p = jnp.pad(lse, (0, n_n * bn - n),
                    constant_values=_PAD_LSE).reshape(1, n_n, bn)
    g_p = jnp.pad(g.astype(jnp.float32), (0, n_n * bn - n)).reshape(1, n_n, bn)

    dh = named_pallas_call(
        "xent_bwd_dh",
        functools.partial(_dh_kernel, n_v=n_v, w_vd=w_vd, bv=bv, v=v),
        grid=(n_n, n_v),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            _w_spec(d, bv, w_vd, lambda i, j: j),
            pl.BlockSpec((1, bv), lambda i, j: (0, j)),
            pl.BlockSpec((1, n_n, bn), lambda i, j: (0, 0, 0)),
            pl.BlockSpec((1, n_n, bn), lambda i, j: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), h.dtype),
        scratch_shapes=[pltpu.VMEM((bn, d), jnp.float32)],
        interpret=interpret,
    )(h, w, bvec, lse_p, g_p)

    dw_shape = (v, d) if w_vd else (d, v)
    dw_scratch = pltpu.VMEM((bv, d) if w_vd else (d, bv), jnp.float32)
    dw, db = named_pallas_call(
        "xent_bwd_dw",
        functools.partial(_dwdb_kernel, n_n=n_n, w_vd=w_vd, bn=bn, bv=bv,
                          n=n, v=v),
        grid=(n_v, n_n),
        in_specs=[
            pl.BlockSpec((bn, d), lambda j, i: (i, 0)),
            _w_spec(d, bv, w_vd, lambda j, i: j),
            pl.BlockSpec((1, bv), lambda j, i: (0, j)),
            pl.BlockSpec((1, n_n, bn), lambda j, i: (0, 0, 0)),
            pl.BlockSpec((1, n_n, bn), lambda j, i: (0, 0, 0)),
        ],
        out_specs=(
            _w_spec(d, bv, w_vd, lambda j, i: j),
            pl.BlockSpec((1, bv), lambda j, i: (0, j)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct(dw_shape, w.dtype),
            jax.ShapeDtypeStruct((1, v), jnp.float32),
        ),
        scratch_shapes=[
            dw_scratch,
            pltpu.VMEM((_LANES, bv), jnp.float32),
        ],
        interpret=interpret,
    )(h, w, bvec, lse_p, g_p)
    return dh, dw, db[0]


# ----------------------------------------------------------------- public op

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _mls(h, w, b, n_block, v_block, interpret, w_layout):
    lse, _ = _mls_fwd(h, w, b, n_block, v_block, interpret, w_layout)
    return lse


def matmul_logsumexp(h, w, b, n_block: int = DEFAULT_N_BLOCK,
                     v_block: int = DEFAULT_V_BLOCK,
                     interpret: bool = None, w_layout: str = "dv"):
    """``logsumexp(h @ w + b, axis=-1)`` without materializing the logits.

    h: [N, D] (bf16/f32); w: [D, V] (``w_layout="dv"``, flax Dense kernel) or
    [V, D] (``w_layout="vd"``, reference softmax_w layout); b: [V] or None.
    Returns f32 [N]. Differentiable in h, w, b (custom VJP recomputes logits
    tiles from the saved lse); dw returns in w's stored layout and dtype.

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


def fused_softmax_xent(h, w, targets, b=None, n_block: int = DEFAULT_N_BLOCK,
                       v_block: int = DEFAULT_V_BLOCK,
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
