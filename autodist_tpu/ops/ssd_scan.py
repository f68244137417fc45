"""Chunked state-space scan — the token mixer of the Mamba-2 hybrids
(Nemotron-H), plain and as two pallas TPU kernels, forward and backward.

``ssd_scan(x [B, L, H, P], dt [B, L, H], A [H], B [B, L, G, N], C [B, L, G, N],
D [H]) -> y [B, L, H, P]``: per sequence and head ``h`` of group ``g = h //
(H / G)``, with ``a_t = dt_t * A_h`` (``A < 0``, ``dt > 0``: a decay)::

    S_t = exp(a_t) S_{t-1} + dt_t * x_t B_t^T          S in R^{P x N}, S_0 = 0
    y_t = S_t C_t + D_h x_t

(Dao & Gu 2024, "state-space duality"). No state a token is ever built. A
sequence is cut into chunks of ``Q`` positions; with ``cum_t`` the running
sum of ``a`` inside a chunk, ``xd = dt * x`` and ``S`` the state that enters
the chunk::

    y_t    = sum_{s<=t} exp(cum_t - cum_s) (C_t . B_s) xd_s            the quadratic form, [Q, Q]
             + exp(cum_t) S C_t + D x_t                                what came before the chunk
    S_next = exp(cum_Q) S + sum_s exp(cum_Q - cum_s) xd_s B_s^T        one [P, N] state a chunk and head

``a``, its running sums and every ``exp`` are float32; the products take
their operands in ``x.dtype`` (bfloat16 in the models) and accumulate in
float32; ``C . B`` is computed once a group and shared by its heads.
Sequences of a batch never share state; a length that is not a multiple of
the chunk is padded with ``dt = 0`` rows (no decay, nothing added), whose
outputs are dropped.

The custom VJP keeps the inputs and the state that enters each chunk
(``[B, L/Q, H, P, N]`` float32: 134 MB a layer at 8,192 tokens, 64 heads of 64
x 128), never a ``[Q, Q]`` plane. The backward is its own chunked scan in
reverse: the cotangent of the state runs from the last chunk to the first,
``dS = exp(cum_Q) dS_next + (exp(cum) dy)^T C``, and with it every chunk's
gradients follow from the chunk alone.

- ``impl="xla"``: the equations above in ``jax.numpy``, every chunk at once
  and the two state recurrences as ``lax.scan`` over ``[B, H, P, N]``; the
  chunk's gradients by autodiff of the chunk's own function on the saved
  state. Init, the CPU and the comparison run it.
- ``impl="pallas"``: ``ssd_fwd`` and ``ssd_bwd``, grid (sequence, group,
  chunk) with the chunks in order (reversed for the backward) and the
  group's ``H / G`` states in VMEM between them. A grid step reads the
  chunk's ``B`` and ``C`` once for the group's heads, builds ``C B^T`` once,
  and per head the decay plane, the masked product and five (forward) or
  eleven (backward) products of 128 x 128 x 64; the backward sums ``dB`` and
  ``dC`` over the group's heads in the step. ``cum`` is XLA's (a cumsum over
  ``[B, L, H]`` float32, nothing beside the rest), handed over in column and
  in row form so the kernel transposes nothing; the small per-position
  gradients (of ``cum``, ``dt``, ``D``) leave the kernel the same way and XLA
  finishes them (a reversed cumsum, three reductions over ``[B, L, H]``).

**What a caller hands, and what that costs around the kernels.** The kernels
address ``x`` and ``y`` as ``[b, L, H P]`` rows, ``B`` and ``C`` as ``[b, L, G
N]`` rows and the states as ``[b, chunks, G, R P, N]``. A call that hands
``x [b, L, H, P]`` and ``B``, ``C [b, L, G, N]`` (cut out of the
convolution's output and reshaped, as every caller did before PR 49) has XLA
write each of them out first and put ``dx``, ``dB``, ``dC`` together after:
on the chip ``[b, L, H, P]`` and ``[b, L, H P]`` are different bytes, tiles
of 8 x 128 over the last two dimensions. A call that leaves ``B`` and ``C``
out hands the convolution's ``[b, L, H P + 2 G N]`` rows whole (``groups =
(G, N)`` says how the columns divide): the three are read as column blocks
of that one array (``B`` begins at block ``H P / N``, so ``H P`` is a
multiple of ``N``), ``y`` comes back, is named and is kept as the ``[b, L, H
P]`` rows ``ssd_fwd`` wrote, ``dy`` is taken as rows, and ``ssd_bwd`` writes
``dx`` into d ``[x | B | C]`` where it lies, whose last ``2 G N`` columns the
two narrow results then fill in place. The operand handed is the signal;
the values are the same bit for bit, and a four-dimensional call traces
what it always traced. ``dt`` and ``cum`` still cross the boundary in
column form ``[b, G, L, R]`` whose last dimension the chip pads to 128
lanes (six arrays of 33.5 MB a backward call at the Nemotron cell's shape:
PERF.md section 7, "Open after PR 49").

On the CPU backend the kernels run in pallas interpret mode;
``tests/test_chip_compile.py`` compiles them for a described v5e at the
Nemotron cell's shape.
"""

import functools
import importlib

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu import telemetry
from autodist_tpu.ops.named_call import named_pallas_call

# The module, as ``grouped_matmul`` reads it: a compile rehearsal that steers
# the flash kernels to compile steers these too.
_flash = importlib.import_module("autodist_tpu.ops.flash_attention")

IMPLS = ("xla", "pallas")
_VMEM_LIMIT = 48 << 20
# ``jax.ad_checkpoint.checkpoint_name`` of what the forward rule makes: the
# output and the chunks' states
KEPT_NAME = "ssd_residuals"


# ------------------------------------------------------------ the equations

def _grouped(x, dt, B, C, chunk: int):
    """``[b, L, ...] -> [b, chunks, Q, G, ...]``: positions cut into chunks,
    heads into their groups (``R = H / G`` heads share a ``B`` and a ``C``)."""
    b, length, h, p = x.shape
    g, n = B.shape[2:]
    nc = length // chunk
    return (x.reshape(b, nc, chunk, g, h // g, p),
            dt.reshape(b, nc, chunk, g, h // g),
            B.reshape(b, nc, chunk, g, n), C.reshape(b, nc, chunk, g, n))


def _running(dt, A):
    """``cum [b, c, Q, G, R]`` float32: the running sum of ``a = dt * A``
    inside each chunk."""
    a = dt.astype(jnp.float32) * A.astype(jnp.float32).reshape(dt.shape[3:])
    return jnp.cumsum(a, axis=2)


def _product(subscripts, a, b, dtype):
    return jnp.einsum(subscripts, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def _chunk_added(x, dt, cum, B):
    """What a chunk adds to the state it was handed: ``[b, c, G, R, P, N]``."""
    to_end = jnp.exp(cum[:, :, -1:] - cum) * dt.astype(jnp.float32)
    return _product("bcqgrp,bcqgn->bcgrpn",
                    x.astype(jnp.float32) * to_end[..., None], B, x.dtype)


def _chunk_end(cum):
    """``exp(cum_Q) [b, c, G, R, 1, 1]``: what a chunk leaves of that state."""
    return jnp.exp(cum[:, :, -1])[..., None, None]


def _chunk_y(x, dt, cum, B, C, D, state):
    """``y [b, c, Q, G, R, P]`` float32 of every chunk from the state that
    enters it."""
    q = x.shape[2]
    scores = _product("bcqgn,bcsgn->bcgqs", C, B, x.dtype)
    t, s = jnp.arange(q)[:, None], jnp.arange(q)[None, :]
    by_head = jnp.moveaxis(cum, 2, -1)                      # [b, c, G, R, Q]
    seg = by_head[..., :, None] - by_head[..., None, :]     # [b, c, G, R, t, s]
    plane = jnp.exp(jnp.where(t >= s, seg, -jnp.inf)) * scores[:, :, :, None]
    xf = x.astype(jnp.float32)
    y = _product("bcgrts,bcsgrp->bctgrp", plane,
                 xf * dt.astype(jnp.float32)[..., None], x.dtype)
    before = _product("bcqgn,bcgrpn->bcqgrp", C, state, x.dtype)
    d = D.astype(jnp.float32).reshape(x.shape[3:5])[..., None]
    return y + jnp.exp(cum)[..., None] * before + d * xf


def _scan_states(end, added, reverse: bool = False):
    """``S_{c+1} = end_c S_c + added_c`` from zeros, over the chunk axis (1);
    returns the state each chunk is handed. ``reverse``: from the last chunk
    down."""
    def step(state, chunk):
        e, a = chunk
        return e * state + a, state
    _, states = jax.lax.scan(
        step, jnp.zeros_like(added[:, 0]),
        (jnp.moveaxis(end, 1, 0), jnp.moveaxis(added, 1, 0)), reverse=reverse)
    return jnp.moveaxis(states, 0, 1)


def _xla_forward(x, dt, A, B, C, D, chunk: int):
    """``(y [b, L, H, P], states [b, c, G, R, P, N] float32)``."""
    xs, dts, Bs, Cs = _grouped(x, dt, B, C, chunk)
    cum = _running(dts, A)
    states = _scan_states(_chunk_end(cum), _chunk_added(xs, dts, cum, Bs))
    y = _chunk_y(xs, dts, cum, Bs, Cs, D, states)
    return y.reshape(x.shape).astype(x.dtype), states


def _xla_backward(x, dt, A, B, C, D, states, dy, chunk: int):
    xs, dts, Bs, Cs = _grouped(x, dt, B, C, chunk)
    dys = dy.reshape(xs.shape)
    cum = _running(dts, A)
    # the state's cotangent, from the last chunk down: what a chunk's y gives
    # to the state it was handed, and what the chunk leaves of the next one's
    given = _product("bcqgrp,bcqgn->bcgrpn",
                     dys.astype(jnp.float32) * jnp.exp(cum)[..., None], Cs,
                     x.dtype)
    d_next = _scan_states(_chunk_end(cum), given, reverse=True)

    def chunks(x, dt, A, B, C, D):
        xs, dts, Bs, Cs = _grouped(x, dt, B, C, chunk)
        cum = _running(dts, A)
        left = _chunk_end(cum) * states + _chunk_added(xs, dts, cum, Bs)
        return _chunk_y(xs, dts, cum, Bs, Cs, D, states), left

    _, transpose = jax.vjp(chunks, x, dt, A, B, C, D)
    return transpose((dys.astype(jnp.float32), d_next))


# ----------------------------------------------------------------- kernels

def _heads(ref, r: int, p: int):
    return ref[0, :, r * p:(r + 1) * p]


def _planes(cum_col, cum_row, mask):
    """``exp(cum_t - cum_s)`` for ``s <= t``, zero above: ``[Q, Q]``."""
    return jnp.exp(jnp.where(mask, cum_col - cum_row, -jnp.inf))


def _last(cum, is_last):
    """``cum_Q [1, 1]`` of a ``[Q, 1]`` column, as a sum under a mask: a
    ``[1, 1]`` slice at sublane ``Q - 1`` cannot be broadcast over a ``[P, N]``
    state (Mosaic: "broadcast in both sublanes and lanes")."""
    return jnp.sum(jnp.where(is_last, cum, 0.0), axis=0, keepdims=True)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def _fwd_kernel(x_ref, b_ref, c_ref, dtc_ref, cumc_ref, cumr_ref, d_ref,
                y_ref, states_ref, state_ref, *, heads: int, p: int):
    q = x_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk_of_a_sequence():
        state_ref[...] = jnp.zeros_like(state_ref)

    states_ref[0, 0, 0] = state_ref[...]
    dtype = x_ref.dtype
    bm, cm = b_ref[0], c_ref[0]
    scores = _dot(cm, bm, (1, 1))                               # [Q(t), Q(s)]
    mask = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    is_last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    for r in range(heads):
        cum = cumc_ref[0, 0, :, r:r + 1]                        # [Q, 1]
        plane = _planes(cum, cumr_ref[0, 0, r:r + 1, :], mask) * scores
        xr = _heads(x_ref, r, p).astype(jnp.float32)
        xd = xr * dtc_ref[0, 0, :, r:r + 1]
        rows = slice(r * p, (r + 1) * p)
        state = state_ref[rows, :]                              # [P, N]
        y = _dot(plane.astype(dtype), xd.astype(dtype), (1, 0))
        y += jnp.exp(cum) * _dot(cm, state.astype(dtype), (1, 1))
        y += d_ref[0, :, rows] * xr
        y_ref[0, :, rows] = y.astype(y_ref.dtype)
        last = _last(cum, is_last)
        added = _dot((xd * jnp.exp(last - cum)).astype(dtype), bm, (0, 0))
        state_ref[rows, :] = jnp.exp(last) * state + added


def _bwd_kernel(x_ref, b_ref, c_ref, dy_ref, dtc_ref, cumc_ref, cumr_ref,
                d_ref, states_ref, dx_ref, db_ref, dc_ref, dcumc_ref,
                dcumr_ref, ddt_ref, dyx_ref, dstate_ref, *, heads: int, p: int):
    q = x_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk_of_a_sequence():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    dtype = x_ref.dtype
    bm, cm = b_ref[0], c_ref[0]
    scores = _dot(cm, bm, (1, 1))
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    mask = row >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    is_last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    d_scores = jnp.zeros((q, q), jnp.float32)
    db = jnp.zeros(bm.shape, jnp.float32)
    dc = jnp.zeros(cm.shape, jnp.float32)
    for r in range(heads):
        rows = slice(r * p, (r + 1) * p)
        cum = cumc_ref[0, 0, :, r:r + 1]
        dt = dtc_ref[0, 0, :, r:r + 1]
        decay = _planes(cum, cumr_ref[0, 0, r:r + 1, :], mask)
        plane = decay * scores
        xr = _heads(x_ref, r, p).astype(jnp.float32)
        dyr = _heads(dy_ref, r, p)
        dyf = dyr.astype(jnp.float32)
        xd = xr * dt
        state = states_ref[0, 0, 0, rows, :]
        d_next = dstate_ref[rows, :]
        last = _last(cum, is_last)
        to_end, from_start = jnp.exp(last - cum), jnp.exp(cum)
        # the quadratic form
        d_plane = _dot(dyr, xd.astype(dtype), (1, 1))            # [Q(t), Q(s)]
        d_scores += decay * d_plane
        moved = d_plane * plane
        d_cum = jnp.sum(moved, axis=1, keepdims=True)
        dcumr_ref[0, 0, r:r + 1, :] = -jnp.sum(moved, axis=0, keepdims=True)
        # what the chunk adds to the state: xd and B under the decay to its end
        left = to_end * _dot(bm, d_next.astype(dtype), (1, 1))   # [Q, P]
        d_xd = _dot(plane.astype(dtype), dyr, (0, 0)) + left
        db += _dot((xd * to_end).astype(dtype), d_next.astype(dtype), (1, 0))
        through_end = jnp.sum(left * xd, axis=1, keepdims=True)
        at_end = (jnp.sum(through_end, axis=0, keepdims=True)
                  + jnp.exp(last) * jnp.sum(d_next * state, keepdims=True))
        d_cum += jnp.where(is_last, at_end, 0.0) - through_end
        # what the state handed to the chunk gives y
        lit = dyf * from_start                                   # [Q, P]
        before = _dot(cm, state.astype(dtype), (1, 1))           # [Q, P]
        d_cum += jnp.sum(lit * before, axis=1, keepdims=True)
        dc += _dot(lit.astype(dtype), state.astype(dtype), (1, 0))
        dstate_ref[rows, :] = (jnp.exp(last) * d_next
                               + _dot(lit.astype(dtype), cm, (0, 0)))
        dx_ref[0, :, rows] = (dt * d_xd + d_ref[0, :, rows] * dyf
                              ).astype(dx_ref.dtype)
        dcumc_ref[0, 0, :, r:r + 1] = d_cum
        ddt_ref[0, 0, :, r:r + 1] = jnp.sum(d_xd * xr, axis=1, keepdims=True)
        dyx_ref[0, 0, :, r:r + 1] = jnp.sum(dyf * xr, axis=1, keepdims=True)
    d_scores = d_scores.astype(dtype)
    db_ref[0] = (db + _dot(d_scores, cm, (0, 0))).astype(db_ref.dtype)
    dc_ref[0] = (dc + _dot(d_scores, bm, (1, 0))).astype(dc_ref.dtype)


# ------------------------------------------------------------------- calls

def _layouts(x, dt, A, B, C, D, chunk: int, sizes):
    """The arrays as the kernels read them: heads and groups folded into the
    lanes of ``x``, ``B`` and ``C`` (``B`` None: ``x`` is ``[b, L, H P + 2 G
    N]`` rows that hold all three, handed three times as they lie); ``dt``
    and ``cum`` a group in column form ``[b, G, L, R]`` and ``cum`` in row
    form ``[b, G, R, L]`` too; ``D`` a row of ``H * P`` lanes a group."""
    b, length, h, p, g, n, r = sizes
    dtf = dt.astype(jnp.float32)
    a = dtf * A.astype(jnp.float32)
    # heads on the lanes for the running sum ([.., chunk, 64]): a [.., 8, 8]
    # tail pads every chunk's sum to 128 lanes, 15 ms a step (PERF.md section 6)
    cum = jnp.cumsum(a.reshape(b, length // chunk, chunk, h), axis=2)
    cum = cum.reshape(b, length, g, r)
    col = lambda t: jnp.moveaxis(t.reshape(b, length, g, r), 1, 2)  # noqa: E731
    wide = (x, x, x) if B is None else (
        x.reshape(b, length, h * p), B.reshape(b, length, g * n),
        C.reshape(b, length, g * n))
    return (*wide, col(dtf), col(cum), jnp.moveaxis(cum, 1, 3),
            jnp.repeat(D.astype(jnp.float32), p).reshape(g, 1, r * p))


def _specs(chunk: int, sizes, order, rows: bool):
    """Block specs by name, for a grid (sequence, group, chunk) whose chunk
    index maps through ``order`` (the backward walks down). ``rows``: ``B``
    and ``C`` are read out of the ``[b, L, H P + 2 G N]`` rows behind ``x``,
    ``B`` from column block ``H P / N`` on and ``C`` ``G`` blocks further."""
    _, _, h, p, g, n, r = sizes
    group = pl.BlockSpec((1, chunk, n), lambda b, g, c: (b, order(c), g))
    behind = lambda first: pl.BlockSpec(  # noqa: E731
        (1, chunk, n), lambda b, g, c: (b, order(c), g + first))
    return dict(
        lanes=pl.BlockSpec((1, chunk, r * p), lambda b, g, c: (b, order(c), g)),
        group=group,
        B=behind(h * p // n) if rows else group,
        C=behind(h * p // n + g) if rows else group,
        col=pl.BlockSpec((1, 1, chunk, r), lambda b, g, c: (b, g, order(c), 0)),
        row=pl.BlockSpec((1, 1, r, chunk), lambda b, g, c: (b, g, 0, order(c))),
        d=pl.BlockSpec((1, 1, r * p), lambda b, g, c: (g, 0, 0)),
        states=pl.BlockSpec((1, 1, 1, r * p, n),
                            lambda b, g, c: (b, order(c), g, 0, 0)))


def _sizes(x, dt, B, groups=None):
    """``(b, L, H, P, G, N, R)``; ``B`` None: of ``x [b, L, H P + 2 G N]``
    rows and ``groups = (G, N)``."""
    b, length, h = dt.shape
    if B is None:
        g, n = groups
        p = (x.shape[2] - 2 * g * n) // h
    else:
        p, (g, n) = x.shape[3], B.shape[2:]
    return b, length, h, p, g, n, h // g


def _forward_call(x, dt, A, B, C, D, chunk: int, interpret: bool,
                  groups=None):
    sizes = b, length, h, p, g, n, r = _sizes(x, dt, B, groups)
    rows = B is None
    nc = length // chunk
    spec = _specs(chunk, sizes, lambda c: c, rows)
    y, states = named_pallas_call(
        "ssd_fwd", functools.partial(_fwd_kernel, heads=r, p=p),
        grid=(b, g, nc),
        in_specs=[spec["lanes"], spec["B"], spec["C"], spec["col"],
                  spec["col"], spec["row"], spec["d"]],
        out_specs=[spec["lanes"], spec["states"]],
        out_shape=[jax.ShapeDtypeStruct((b, length, h * p), x.dtype),
                   jax.ShapeDtypeStruct((b, nc, g, r * p, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((r * p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*_layouts(x, dt, A, B, C, D, chunk, sizes))
    if rows:
        return y, states
    return y.reshape(x.shape), states.reshape(b, nc, g, r, p, n)


def _backward_call(x, dt, A, B, C, D, states, dy, chunk: int, interpret: bool,
                   groups=None):
    sizes = b, length, h, p, g, n, r = _sizes(x, dt, B, groups)
    rows = B is None
    nc = length // chunk
    spec = _specs(chunk, sizes, lambda c: nc - 1 - c, rows)
    x2, b2, c2, dtc, cumc, cumr, d2 = _layouts(x, dt, A, B, C, D, chunk, sizes)
    small = jax.ShapeDtypeStruct((b, g, length, r), jnp.float32)
    narrow = jax.ShapeDtypeStruct((b, length, g * n), x.dtype)
    dx, db, dc, d_cum_col, d_cum_row, d_dt, dyx = named_pallas_call(
        "ssd_bwd", functools.partial(_bwd_kernel, heads=r, p=p),
        grid=(b, g, nc),
        in_specs=[spec["lanes"], spec["B"], spec["C"], spec["lanes"],
                  spec["col"], spec["col"], spec["row"], spec["d"],
                  spec["states"]],
        out_specs=[spec["lanes"], spec["group"], spec["group"], spec["col"],
                   spec["row"], spec["col"], spec["col"]],
        # rows: dx is written where it lies in d [x | B | C], whose other
        # columns the two narrow results then fill
        out_shape=[jax.ShapeDtypeStruct(x2.shape, x.dtype),
                   narrow if rows else jax.ShapeDtypeStruct(b2.shape, B.dtype),
                   narrow if rows else jax.ShapeDtypeStruct(c2.shape, C.dtype),
                   small,
                   jax.ShapeDtypeStruct((b, g, r, length), jnp.float32),
                   small, small],
        scratch_shapes=[pltpu.VMEM((r * p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(x2, b2, c2, dy.astype(x.dtype).reshape(b, length, h * p), dtc, cumc,
      cumr, d2, states.reshape(b, nc, g, r * p, n))
    # [b, G, L, R] -> [b, L, H]; the gradient of a running sum is the sum of
    # what follows, inside the chunk
    flat = lambda t: jnp.moveaxis(t, 2, 1).reshape(b, length, h)  # noqa: E731
    d_cum = flat(d_cum_col) + jnp.moveaxis(d_cum_row, 3, 1).reshape(b, length, h)
    d_a = jnp.flip(jnp.cumsum(jnp.flip(
        d_cum.reshape(b, nc, chunk, h), axis=2), axis=2), axis=2
    ).reshape(b, length, h)
    Af, dtf = A.astype(jnp.float32), dt.astype(jnp.float32)
    d_dt = flat(d_dt) + d_a * Af
    given = (lambda t, like: t) if rows else (  # noqa: E731
        lambda t, like: t.reshape(like.shape))
    grads = (given(dx, x), d_dt.astype(dt.dtype),
             jnp.sum(d_a * dtf, axis=(0, 1)).astype(A.dtype), given(db, B),
             given(dc, C), jnp.sum(flat(dyx), axis=(0, 1)).astype(D.dtype))
    if rows:        # d [x | B | C] whole: the narrow two into their columns
        dx = jax.lax.dynamic_update_slice(dx, db, (0, 0, h * p))
        dx = jax.lax.dynamic_update_slice(dx, dc, (0, 0, h * p + g * n))
        return (dx, *grads[1:3], None, None, grads[5])
    return grads


# --------------------------------------------------------------- public op

def moved_bytes(sizes, dtype, chunk: int):
    """``(forward, backward)`` bytes one call must move, each operand once:
    forward ``x``, ``B``, ``C`` read, ``y`` and one float32 state a chunk and
    head written; backward those read again with ``dy`` and ``dx``, ``dB``,
    ``dC`` written. ``dt`` and ``cum`` ([b, L, H] float32) are nothing beside
    them and left out."""
    b, length, h, p, g, n, _ = sizes
    size = jnp.dtype(dtype).itemsize
    wide, narrow = b * length * h * p * size, b * length * g * n * size
    states = b * -(-length // chunk) * h * p * n * 4
    return (2 * wide + 2 * narrow + states, 3 * wide + 4 * narrow + states)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, dt, A, B, C, D, chunk, impl):
    return _scan_fwd(x, dt, A, B, C, D, chunk, impl)[0]


def _scan_fwd(x, dt, A, B, C, D, chunk, impl):
    if impl == "xla":
        y, states = _xla_forward(x, dt, A, B, C, D, chunk)
    else:
        y, states = _forward_call(x, dt, A, B, C, D, chunk,
                                  _flash._use_interpret())
    # what a caller's ``jax.checkpoint`` may keep by name, so that its
    # backward does not run the forward again (``_flash_fwd``'s comment)
    y, states = checkpoint_name((y, states), KEPT_NAME)
    return y, (x, dt, A, B, C, D, states)


def _scan_bwd(chunk, impl, residuals, dy):
    if impl == "xla":
        return _xla_backward(*residuals, dy, chunk)
    return _backward_call(*residuals, dy, chunk, _flash._use_interpret())


_scan.defvjp(_scan_fwd, _scan_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _scan_rows(xbc, dt, A, D, chunk, groups):
    """The kernels on ``[x | B | C]`` rows as one array (``groups = (G, N)``):
    ``y [b, L, H P]`` rows, named and kept in the shape ``ssd_fwd`` wrote."""
    return _scan_rows_fwd(xbc, dt, A, D, chunk, groups)[0]


def _scan_rows_fwd(xbc, dt, A, D, chunk, groups):
    y, states = checkpoint_name(
        _forward_call(xbc, dt, A, None, None, D, chunk, _flash._use_interpret(),
                      groups), KEPT_NAME)
    return y, (xbc, dt, A, D, states)


def _scan_rows_bwd(chunk, groups, residuals, dy):
    xbc, dt, A, D, states = residuals
    dxbc, d_dt, d_a, _, _, d_d = _backward_call(
        xbc, dt, A, None, None, D, states, dy, chunk, _flash._use_interpret(),
        groups)
    return dxbc, d_dt, d_a, d_d


_scan_rows.defvjp(_scan_rows_fwd, _scan_rows_bwd)


def _check_rows(x, dt, groups, impl: str):
    """The sizes of a call that hands ``[x | B | C]`` as one array of rows,
    or a refusal that names what is wrong with it."""
    if (x.ndim != 3 or dt.ndim != 3 or x.shape[:2] != dt.shape[:2]
            or groups is None or len(groups) != 2):
        raise ValueError(
            f"ssd_scan: B and C left out, so x {x.shape} holds [x | B | C] as "
            f"[B, L, H P + 2 G N] rows beside dt {dt.shape} [B, L, H], and "
            f"groups {groups} says (G, N)")
    h, (g, n) = dt.shape[2], groups
    wide = x.shape[2] - 2 * g * n
    if wide <= 0 or wide % h or h % g:
        raise ValueError(
            f"ssd_scan: {x.shape[2]} columns are not {h} heads of P and twice "
            f"{g} groups of {n}, G dividing H")
    if impl == "pallas" and wide % n:
        raise ValueError(
            f"ssd_scan kernels: B begins at column {wide} of the rows, off a "
            f"block of {n} (the state's width, a multiple of a lane tile's 128)")
    return _sizes(x, dt, None, groups)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, D: jax.Array, chunk: int = 128,
             impl: str = "xla", groups=None) -> jax.Array:
    """``y_t = S_t C_t + D x_t`` with ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
    B_t^T`` (module docstring). x: ``[batch, L, H, P]``; dt: ``[batch, L, H]``
    (positive: after its softplus); A, D: ``[H]``; B, C: ``[batch, L, G, N]``,
    ``G`` dividing ``H``; ``impl``: ``"xla"`` or ``"pallas"`` (``N`` and ``H /
    G * P`` multiples of 128, ``chunk`` of 128). Returns ``[batch, L, H, P]``
    in ``x.dtype``. Differentiable in all six; the inputs and one ``[P, N]``
    float32 state a chunk and head are kept for the backward.

    **Rows.** ``B`` and ``C`` None: ``x`` is ``[batch, L, H P + 2 G N]``, the
    convolution's ``[x | B | C]`` as it wrote it, and ``groups = (G, N)``
    says how its columns divide. The kernels read the three where they lie
    (``H P`` a multiple of ``N``), the result is ``[batch, L, H P]`` rows as
    ``ssd_fwd`` writes them, and the backward writes d ``[x | B | C]`` as one
    array: nothing is cut, reshaped or put together around the kernels. The
    values are the four-dimensional call's, bit for bit. The gauge
    ``ssd.operands_relaid`` counts the wide operands a kernel call was handed
    cut apart (x, B, C and the result: 4, or 0 for rows).

    Under a mesh of several devices the kernels run per device
    (:func:`autodist_tpu.parallel.mesh.per_device`), the batch split over the
    data axes."""
    if impl not in IMPLS:
        raise ValueError(f"Unknown ssd impl {impl!r}; valid: {IMPLS}")
    rows = B is None and C is None
    if rows:
        sizes = b, length, h, p, g, n, r = _check_rows(x, dt, groups, impl)
        if impl == "xla":       # the plain path on the three cut apart
            cut = lambda lo, hi, *dims: x[..., lo:hi].reshape(  # noqa: E731
                b, length, *dims)
            y = ssd_scan(cut(0, h * p, h, p), dt, A,
                         cut(h * p, h * p + g * n, g, n),
                         cut(h * p + g * n, h * p + 2 * g * n, g, n), D, chunk)
            return y.reshape(b, length, h * p)
    else:
        if (x.ndim != 4 or dt.shape != x.shape[:3] or B is None or C is None
                or B.shape != C.shape or B.shape[:2] != x.shape[:2]
                or B.ndim != 4 or x.shape[2] % B.shape[2]):
            raise ValueError(
                f"ssd_scan: x {x.shape}, dt {dt.shape}, A {A.shape}, B "
                f"{getattr(B, 'shape', None)}, C {getattr(C, 'shape', None)}, D "
                f"{D.shape}; want [B, L, H, P], [B, L, H], [H], "
                f"[B, L, G, N] twice with G dividing H, [H]")
        sizes = _sizes(x, dt, B)
    b, length, h, p, g, n, r = sizes
    if A.shape != (h,) or D.shape != (h,):
        raise ValueError(f"ssd_scan: A {A.shape}, D {D.shape}; want [H] = "
                         f"[{h}] twice")
    if impl == "pallas" and (n % 128 or (r * p) % 128 or chunk % 128):
        raise ValueError(
            f"ssd_scan kernels: state {n}, a group's heads x head_dim "
            f"{r * p} and chunk {chunk} must be multiples of 128")
    chunks = -(-length // chunk)
    telemetry.counter("ssd.calls").inc()
    telemetry.gauge("ssd.chunk").set(chunk)
    telemetry.gauge("ssd.chunks").set(b * chunks)
    telemetry.gauge("ssd.heads").set(h)
    telemetry.gauge("ssd.groups").set(g)
    telemetry.gauge("ssd.state").set(n)
    fwd_bytes, bwd_bytes = moved_bytes(sizes, x.dtype, chunk)
    telemetry.gauge("ssd.fwd.bytes").set(fwd_bytes)
    telemetry.gauge("ssd.bwd.bytes").set(bwd_bytes)
    if impl == "pallas":
        telemetry.gauge("ssd.operands_relaid").set(0 if rows else 4)
    pad = chunks * chunk - length
    if pad:
        padded = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))  # noqa: E731
        x, dt = padded(x), padded(dt)
        if not rows:
            B, C = padded(B), padded(C)
    from autodist_tpu.parallel.mesh import per_device
    if impl == "xla":
        y = _scan(x, dt, A, B, C, D, chunk=chunk, impl=impl)
    elif rows:
        y = per_device(functools.partial(_scan_rows, chunk=chunk, groups=groups),
                       (x, dt, A, D), batched=(True, True, False, False))
    else:
        y = per_device(functools.partial(_scan, chunk=chunk, impl=impl),
                       (x, dt, A, B, C, D),
                       batched=(True, True, False, True, True, False))
    return y[:, :length] if pad else y
