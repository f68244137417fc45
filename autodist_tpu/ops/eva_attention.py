"""EVA attention (Zheng et al., "Efficient Attention via Control Variates",
ICLR 2023) in the deterministic form EvaByte trains with — pallas TPU kernels,
forward AND backward, and the plain ``jax.numpy`` form they are checked by.

A sequence of ``L`` positions is cut into windows of ``window`` positions and
chunks of ``chunk`` (a window is ``window / chunk`` chunks). Position ``i``
lies in window ``w(i) = i // window``. Per head, with two learned vectors
``phi`` and ``mu`` ``[D]`` and the scale ``s = D^-0.5``::

    a chunk's summary, from its `chunk` (rotated) keys and its values:
      a_cj = softmax_j(s * k_cj . phi)
      k~_c = sum_j a_cj k_cj + mu          v~_c = sum_j a_cj v_cj
    query i sees
      the positions j with w(j) = w(i), j <= i      at logits s * q_i . k_j
      the chunks c of every EARLIER window          at logits s * q_i . k~_c
    under ONE softmax; o_i = sum_j p_ij v_j + sum_c p_ic v~_c

In window 0 it is plain causal attention; a query never sees a summary of its
own window.

**The pooling** (:func:`eva_pool`) is elementwise work and a softmax over
``chunk`` numbers, float32, left to XLA under the named scope ``eva_pool`` and
differentiated by JAX. **The core** (``_kernel_core``) has the custom VJP and,
under ``impl="kernel"``, two kernels:

``eva_fwd``, grid (batch x head, q block): a q block of 512 queries walks the
summaries of the windows before its own, ``[0, w * window / chunk)`` of the
(batch, head)'s resident ``[L / chunk, D]`` — whole tiles of one window's
``window / chunk`` summaries, never masked — and then its window's resident
``[window, D]`` keys in causal tiles of 512, one online softmax through both.
The tile arithmetic is ``ops/flash_attention.py``'s ``_attend_block``, called
twice: the score tile transposed ``[keys, queries]``, the plain tiles in
straight-line blocks, the masked body on the diagonal alone. ``o`` and the
joint per-row log-sum-exp are what the backward needs.

``eva_bwd``, grid (batch x head, window, key block): one pass per window, as
flash's one-pass backward is per (batch, head). The window's q and dO stay in
VMEM with a float32 dQ accumulator across its key blocks; a key block's step
finishes its dK and dV (``_backward_block``: one recomputed score tile, ``p =
exp(s - lse)`` against the JOINT log-sum-exp, feeds dV, dK and dQ). On a
window's first step the same body runs against each earlier window's tile of
summaries, adding to dQ and to the float32 ``d k~`` / ``d v~`` of the (batch,
head), which are output blocks that stay resident across the windows (the
grid's window axis is sequential). ``D_i = rowsum(dO * O)`` stands because
``o`` is the joint result. No gradient with respect to a log-sum-exp exists.

Where the operands lie: q and k have been rotated since their projections, so
they arrive ``[B, L, H, D]`` and XLA lays them out ``[B * H, L, D]`` as it
turns them (ops/flash_attention.py, "Where the operands lie"); v, the result
and dO are a projection's rows ``[B, L, H * D]`` and are read and written
where they lie while ``D`` is whole 128-lane tiles.

``impl="dot"`` is the same mathematics over an explicit ``[L, L + L / chunk]``
score plane: the CPU's path at small sizes and the kernels' check.
"""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu import telemetry
from autodist_tpu.ops.blockwise_attention import NEG_INF
from autodist_tpu.ops.flash_attention import (
    DEFAULT_Q_BLOCK, _attend_block, _backward_block, _finish_dkdv, _head_rows,
    _head_spec, _heads_back, _loop, _scale_is_exact, _stays, _sub_tile,
    _tile_start, _walk_groups, prepare_backward_q_side)
from autodist_tpu.ops.named_call import named_pallas_call

# The module, not the function the package re-exports under the same name:
# ``_use_interpret`` is looked up in it at call time (see grouped_matmul).
_flash = importlib.import_module("autodist_tpu.ops.flash_attention")

KEPT_NAME = "eva_residuals"     # o and the joint log-sum-exp, for a checkpoint's policy
_VMEM_LIMIT = 48 << 20          # the backward holds some 10 MiB; the default scope is 16


def check_shapes(length: int, window: int, chunk: int):
    if window % chunk or length % window:
        raise ValueError(f"EVA needs whole chunks a window and whole windows a "
                         f"sequence: length {length}, window {window}, chunk {chunk}")


def eva_pool(k, v, phi, mu, chunk: int):
    """``(k~, v~)`` ``[B, L / chunk, H, D]``, in ``k``'s and ``v``'s dtypes:
    each chunk's keys and values pooled by a softmax over its ``chunk`` keys'
    products with ``phi`` ``[H, D]`` at the scale ``D^-0.5``, ``mu`` ``[H,
    D]`` added to the pooled key. float32 inside."""
    b, length, h, d = k.shape
    with jax.named_scope("eva_pool"):
        kc = k.reshape(b, length // chunk, chunk, h, d).astype(jnp.float32)
        vc = v.reshape(b, length // chunk, chunk, h, d).astype(jnp.float32)
        logits = jnp.sum(kc * phi.astype(jnp.float32), axis=-1) * d ** -0.5
        a = jax.nn.softmax(logits, axis=2)[..., None]       # over the chunk's keys
        ks = jnp.sum(a * kc, axis=2) + mu.astype(jnp.float32)
        vs = jnp.sum(a * vc, axis=2)
        return ks.astype(k.dtype), vs.astype(v.dtype)


def eva_pairs(length: int, window: int, chunk: int) -> tuple:
    """``(visible, computed)`` (query, key-or-summary) pairs of one (batch,
    head): what the mask keeps, and what the score tiles the forward runs
    hold (a window's ``window / chunk`` summaries against a q block, and the
    causal ``[tile, q block]`` tiles at or under the diagonal; the backward
    walks the same tiles wherever a key tile is a q block, as at the cell's
    512 x 512)."""
    check_shapes(length, window, chunk)
    bq, sub, per_window = _blocks(window, chunk)
    i = np.arange(length)
    visible = int((i % window + 1 + i // window * per_window).sum())
    computed = 0
    for q_lo in range(0, length, bq):
        in_window = q_lo % window
        computed += bq * (q_lo // window * per_window        # summaries, whole tiles
                          + -(-(in_window + bq) // sub) * sub)  # tiles up to the diagonal
    return visible, computed


def _blocks(window: int, chunk: int) -> tuple:
    """``(q block, key tile, summaries a window)``: 512 queries (the whole
    window where it is shorter) and the widest of 512 / 256 / 128 keys that
    divides a window."""
    bq = min(DEFAULT_Q_BLOCK, window)
    if window % bq:
        raise ValueError(f"q block {bq} does not divide the window {window}")
    return bq, _sub_tile(window), window // chunk


# ------------------------------------------------------------------ kernels

def _eva_fwd_kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, lse_ref, *,
                    per_window: int, sub: int, scale: float, blocks_a_window: int):
    qi = pl.program_id(1)
    bq, d = q_ref.shape[1], v_ref.shape[2]
    w = qi // blocks_a_window
    state = (jnp.full((1, bq), NEG_INF, jnp.float32),
             jnp.zeros((1, bq), jnp.float32), jnp.zeros((d, bq), jnp.float32))
    n_windows = ks_ref.shape[1] // per_window
    # the summaries of every earlier window: w whole tiles, none masked
    state = _attend_block(
        q_ref, ks_ref, vs_ref, state, q_lo=0, k_lo=0, valid=w * per_window,
        sub=per_window, causal=False, scale=scale, guard_empty_rows=False,
        groups=_walk_groups(n_windows - 1))
    # its own window's keys, causal
    m, l, acc = _attend_block(
        q_ref, k_ref, v_ref, state, q_lo=(qi % blocks_a_window) * bq, k_lo=0,
        valid=None, sub=sub, causal=True, scale=scale, guard_empty_rows=False,
        groups=_walk_groups(k_ref.shape[1] // sub - 1))
    o_ref[0] = (acc / l).T.astype(o_ref.dtype)
    lse_ref[0, pl.ds(qi, 1), :] = m + jnp.log(l)


def _eva_bwd_kernel(q_ref, do_ref, lse_ref, dd_ref, k_ref, v_ref, ks_ref, vs_ref,
                    dq_ref, dk_ref, dv_ref, dks_ref, dvs_ref,
                    dq_acc, dk_acc, dv_acc, *, per_window: int, sub: int,
                    scale: float):
    w, kb = pl.program_id(1), pl.program_id(2)
    n_t = q_ref.shape[1] // sub            # q tiles a window: rows of the planes
    rows = dict(row0=w * n_t, sub=sub, scale=scale, valid=None, q_lo=0)

    @pl.when((w == 0) & (kb == 0))
    def _first_of_the_head():
        dks_ref[:] = jnp.zeros_like(dks_ref)
        dvs_ref[:] = jnp.zeros_like(dvs_ref)

    @pl.when(kb == 0)
    def _summaries():
        dq_acc[:] = jnp.zeros_like(dq_acc)

        def earlier_window(u, carry):
            at = pl.ds(pl.multiple_of(u * per_window, per_window), per_window)
            _backward_block(
                q_ref, do_ref, lse_ref, dd_ref, ks_ref.at[:, at, :],
                vs_ref.at[:, at, :], dq_acc, dks_ref.at[0, at, :],
                dvs_ref.at[0, at, :], k_lo=0, causal=False, **rows)
            return carry

        jax.lax.fori_loop(0, w, earlier_window, None)

    dk_acc[:] = jnp.zeros_like(dk_acc)
    dv_acc[:] = jnp.zeros_like(dv_acc)
    _backward_block(q_ref, do_ref, lse_ref, dd_ref, k_ref, v_ref,
                    dq_acc, dk_acc, dv_acc, k_lo=kb * k_ref.shape[1],
                    causal=True, **rows)
    _finish_dkdv(dk_ref, dv_ref, dk_acc, dv_acc, scale)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finish():
        def turn(t, carry):
            dq_ref[0, pl.ds(_tile_start(t, sub, n_t), sub), :] = (
                scale * dq_acc[t]).T.astype(dq_ref.dtype)
            return carry

        _loop(0, n_t, turn, None)


def _forward(q, k, v, ks, vs, window: int, chunk: int, interpret: bool):
    """``(o, lse)``: ``o`` as the kernels write it (``[B, L, H * D]`` rows
    where ``D`` is whole lane tiles, else ``[B * H, L, D]``), ``lse`` ``[B *
    H, n_q, bq]`` float32."""
    b, length, h, d = q.shape
    bq, sub, per_window = _blocks(window, chunk)
    n_q, blocks_a_window = length // bq, window // bq
    rows = _stays(d, True)
    qf, kf, vf = _head_rows(q, False), _head_rows(k, False), _head_rows(v, rows)
    ksf, vsf = _head_rows(ks, False), _head_rows(vs, False)

    def own(i):
        return i

    def window_of(i):
        return i // blocks_a_window

    def whole(i):
        return 0

    return named_pallas_call(
        "eva_fwd",
        functools.partial(_eva_fwd_kernel, per_window=per_window, sub=sub,
                          scale=d ** -0.5, blocks_a_window=blocks_a_window),
        grid=(b * h, n_q),
        in_specs=[_head_spec(bq, d, h, False, own),
                  _head_spec(window, d, h, False, window_of),
                  _head_spec(window, d, h, rows, window_of),
                  _head_spec(length // chunk, d, h, False, whole),
                  _head_spec(length // chunk, d, h, False, whole)],
        out_specs=(_head_spec(bq, d, h, rows, own),
                   pl.BlockSpec((1, n_q, bq), lambda bh, i: (bh, 0, 0))),
        out_shape=(jax.ShapeDtypeStruct(vf.shape, q.dtype),
                   jax.ShapeDtypeStruct((b * h, n_q, bq), jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(qf, kf, vf, ksf, vsf)


def _backward(q, k, v, ks, vs, o, lse, g, window: int, chunk: int,
              interpret: bool):
    """``(dq, dk, dv, d k~, d v~)`` as ``[B, L, H, D]`` / ``[B, L / chunk, H,
    D]``; the summaries' gradients float32, as the kernel accumulated them.
    ``o`` as :func:`_forward` wrote it, ``g`` ``[B, L, H, D]``."""
    b, length, h, d = q.shape
    bq, _, per_window = _blocks(window, chunk)
    rows = _stays(d, True)
    qf, dof, dd, _, n_q = prepare_backward_q_side(
        q, _heads_back(o, b, h, length, rows), g, bq, (False, rows))
    kf, vf = _head_rows(k, False), _head_rows(v, rows)
    ksf, vsf = _head_rows(ks, False), _head_rows(vs, False)
    blocks = window // bq          # a key block is a q tile: one score tile a pair
    n_chunks = length // chunk

    def window_rows(w, kb):
        return w

    def key_block(w, kb):
        return w * blocks + kb

    def whole(w, kb):
        return 0

    planes = pl.BlockSpec((1, n_q, bq), lambda bh, w, kb: (bh, 0, 0))
    q_spec = _head_spec(window, d, h, False, window_rows)
    k_spec = _head_spec(bq, d, h, False, key_block)
    v_spec = _head_spec(bq, d, h, rows, key_block)
    s_spec = _head_spec(n_chunks, d, h, False, whole)
    summaries = jax.ShapeDtypeStruct((b * h, n_chunks, d), jnp.float32)
    dq, dk, dv, dks, dvs = named_pallas_call(
        "eva_bwd",
        functools.partial(_eva_bwd_kernel, per_window=per_window, sub=bq,
                          scale=d ** -0.5),
        grid=(b * h, length // window, blocks),
        in_specs=[q_spec, _head_spec(window, d, h, rows, window_rows), planes,
                  planes, k_spec, v_spec, s_spec, s_spec],
        out_specs=(q_spec, k_spec, v_spec, s_spec, s_spec),
        out_shape=(jax.ShapeDtypeStruct(qf.shape, q.dtype),
                   jax.ShapeDtypeStruct(kf.shape, k.dtype),
                   jax.ShapeDtypeStruct(vf.shape, v.dtype), summaries, summaries),
        scratch_shapes=[pltpu.VMEM((window // bq, d, bq), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(qf, dof, lse, dd, kf, vf, ksf, vsf)
    # the summaries' dK left the kernel as a key block's accumulator does
    # before _finish_dkdv: unscaled unless the scale went onto q exactly
    scale = d ** -0.5
    if not _scale_is_exact(scale):
        dks = scale * dks
    return (_heads_back(dq, b, h, length, False),
            _heads_back(dk, b, h, length, False),
            _heads_back(dv, b, h, length, rows),
            _heads_back(dks, b, h, n_chunks, False),
            _heads_back(dvs, b, h, n_chunks, False))


# ------------------------------------------------------------ the operator

def _dot_core(q, k, v, ks, vs, window: int, chunk: int):
    """The quadratic form: one ``[L, L + L / chunk]`` float32 score plane a
    head, the window's causal mask beside the earlier windows' summaries."""
    length, d = q.shape[1], q.shape[-1]
    i = jnp.arange(length)[:, None]
    j = jnp.arange(length)[None, :]
    c = jnp.arange(length // chunk)[None, :]
    visible = jnp.concatenate(
        [(j <= i) & (j // window == i // window),
         c // (window // chunk) < i // window], axis=1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.concatenate([k, ks], axis=1),
                        preferred_element_type=jnp.float32)
    scores = jnp.where(visible, scores * d ** -0.5, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs,
                      jnp.concatenate([v, vs], axis=1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kernel_core(q, k, v, ks, vs, window, chunk):
    """The one softmax over a window's keys and the earlier windows'
    summaries ``ks``, ``vs`` ``[B, L / chunk, H, D]``, through the two
    kernels: ``[B, L, H, D]``."""
    return _kernel_core_fwd(q, k, v, ks, vs, window, chunk)[0]


def _kernel_core_fwd(q, k, v, ks, vs, window, chunk):
    # Named so that a caller's ``jax.checkpoint`` whose policy lists
    # ``KEPT_NAME`` keeps them and does not launch the forward kernel again
    # for its backward (the identity, lowered to nothing, anywhere else);
    # named as the kernel wrote them.
    o, lse = checkpoint_name(
        _forward(q, k, v, ks, vs, window, chunk, _flash._use_interpret()),
        KEPT_NAME)
    b, length, h, d = q.shape
    return (_heads_back(o, b, h, length, _stays(d, True)),
            (q, k, v, ks, vs, o, lse))


def _kernel_core_bwd(window, chunk, residuals, g):
    q, k, v, ks, vs, o, lse = residuals
    dq, dk, dv, dks, dvs = _backward(q, k, v, ks, vs, o, lse, g, window, chunk,
                                     _flash._use_interpret())
    return dq, dk, dv, dks.astype(ks.dtype), dvs.astype(vs.dtype)


_kernel_core.defvjp(_kernel_core_fwd, _kernel_core_bwd)


def eva_attention(q, k, v, phi, mu, *, window: int, chunk: int,
                  impl: str = "kernel"):
    """EVA attention over ``[B, L, H, D]`` tensors (``q`` and ``k`` rotated
    already), ``phi`` and ``mu`` ``[H, D]``: the module docstring's
    equations. ``impl="kernel"``: the pallas forward and backward (interpreted
    on the CPU); ``"dot"``: the quadratic form. Under a mesh of several
    devices the kernels run per device on its share of the batch. Gauges
    ``eva.windows`` and ``eva.summaries``: of one sequence."""
    from autodist_tpu.parallel.mesh import per_device
    check_shapes(q.shape[1], window, chunk)
    if impl not in ("dot", "kernel"):
        raise ValueError(f"Unknown impl {impl!r}; valid: 'dot', 'kernel'")
    core = _dot_core if impl == "dot" else _kernel_core
    telemetry.gauge("eva.windows").set(q.shape[1] // window)
    telemetry.gauge("eva.summaries").set(q.shape[1] // chunk)

    def call(q, k, v, phi, mu):
        return core(q, k, v, *eva_pool(k, v, phi, mu, chunk), window, chunk)

    return per_device(call, (q, k, v, phi, mu),
                      batched=(True, True, True, False, False))
