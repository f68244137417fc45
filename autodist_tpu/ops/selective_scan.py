"""Selective scan — the token mixer of the Mamba-1 hybrids (Jamba), plain and
as two pallas TPU kernels, forward and backward.

``selective_scan(x [B, L, E], dt [B, L, E], A [E, N], B [B, L, N], C [B, L, N],
D [E]) -> y [B, L, E]``: per sequence, channel ``e`` and state index ``n``
(``A < 0``, ``dt > 0``: a decay a channel *and* a state)::

    s_t[e, n] = exp(dt_t[e] A[e, n]) s_{t-1}[e, n] + dt_t[e] x_t[e] B_t[n]      s_0 = 0
    y_t[e]    = sum_n s_t[e, n] C_t[n] + D[e] x_t[e]

(Gu & Dao 2023, "Mamba"). ``ops/ssd_scan.py`` cannot compute it: there ``A``
is one scalar a head and a chunk is a masked ``C B^T`` product on the MXU;
here the decay differs by channel and state, ``B`` and ``C`` are shared by all
channels, so there is no head and no product to hand the MXU: ``E N`` state
elements a token (81,920 at Jamba's 5,120 x 16), each an ``exp`` and five
multiply-adds, on the VPU and the EUP.

No ``[L, E, N]`` array is ever built (5.4 GB at 16,384 tokens). A sequence is
cut into chunks of ``chunk`` positions; the custom VJP keeps the inputs and the
float32 ``[E, N]`` state that enters each chunk (328 KB), and the backward
walks the chunks from the last to the first: it makes a chunk's states again
from the one that entered it, then runs the tokens in reverse with the state's
cotangent ``ds_{t-1} = exp(dt_t A) ds_t`` carried in fast memory.

- ``impl="xla"``: the recurrence in ``jax.numpy`` float32, ``lax.scan`` over
  the chunks and over a chunk's tokens; the backward is autodiff of one
  chunk's function on the saved state, chunk by chunk in reverse. Init, the
  CPU and the comparison run it.
- ``impl="pallas"``: ``selective_scan_fwd`` and ``selective_scan_bwd``. The
  channels fill whole vector registers: a grid step holds 1,024 channels (8
  sublanes x 128 lanes) of ``chunk`` tokens, so a token's ``x``, ``dt`` and
  ``y`` are one register each and the state is ``N`` registers, carried
  through the token loop in registers and between chunks in VMEM. ``x``,
  ``dt``, ``dy`` are read and ``y``, ``dx``, ``ddt`` written as the ``[B, L,
  E]`` arrays the model holds, in blocks of ``[chunk, 1,024]`` rows: a row is
  one sublane of eight neighbouring ``[8, 128]`` tiles, which a load or a
  store with a sublane stride of 8 makes the token's register with no
  arithmetic (:func:`_row`), so XLA lays nothing out anew around a call
  (``[B, L, E / 128, 128]``, one token a tile, is another tiling on the chip:
  a pass over memory an operand). A packed dtype has no single row to load
  (a bfloat16 tile holds 16 tokens): such ``x`` / ``dy`` are widened a chunk
  at a time into a float32 VMEM block and ``y`` / ``dx`` narrowed out of one,
  the conversion a token's loop would otherwise make. ``B_t[n]`` and
  ``C_t[n]`` are scalars (SMEM), so nothing is broadcast across lanes and
  ``y``'s sum over ``n`` is a sum of registers. All arithmetic is float32
  whatever ``x``'s dtype. Forward grid (sequence, channel tile, chunk);
  backward grid (sequence, chunk reversed, channel tile): the sums over the
  channels that ``dB`` and ``dC`` need are gathered a (token, state) register
  over the channel tiles in VMEM and reduced once a chunk, sublanes on the XLU
  and lanes by a product with ones on the otherwise idle MXU, which also lays
  the result out with the states on the lanes.

``silu(z)`` gating stays outside (XLA fuses it into the output projection's
operand); the ``D`` term is inside.

On the CPU backend the kernels run in pallas interpret mode;
``tests/test_chip_compile.py`` compiles them for a described v5e at the Jamba
cell's shape.
"""

import functools
import importlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu import telemetry
from autodist_tpu.ops.named_call import named_pallas_call

# The module, as ``ssd_scan`` reads it: a compile rehearsal that steers the
# flash kernels to compile steers these too.
_flash = importlib.import_module("autodist_tpu.ops.flash_attention")

IMPLS = ("xla", "pallas")
DEFAULT_CHUNK = 128
_LANES = 128
_TILE = 8 * _LANES          # channels a grid step holds: one register a token
_VMEM_LIMIT = 64 << 20
_LN2 = 0.6931471805599453


# ------------------------------------------------------------ the equations

def _chunk(state, x, dt, A, B, C, D):
    """One chunk of one batch, token by token: ``state [b, E, N]``, ``x, dt
    [b, Q, E]``, ``B, C [b, Q, N]`` -> ``(state after, y [b, Q, E])``."""
    def token(s, row):
        x_t, dt_t, b_t, c_t = row
        s = (jnp.exp(dt_t[..., None] * A) * s
             + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return s, jnp.sum(s * c_t[:, None, :], axis=-1) + D * x_t
    rows = tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, B, C))
    state, y = jax.lax.scan(token, state, rows)
    return state, jnp.moveaxis(y, 0, 1)


def _chunked(t, chunk: int):
    """``[b, L, ...] -> [chunks, b, Q, ...]``."""
    b, length = t.shape[:2]
    return jnp.moveaxis(t.reshape(b, length // chunk, chunk, *t.shape[2:]), 1, 0)


def _f32(*arrays):
    return tuple(a.astype(jnp.float32) for a in arrays)


def _xla_forward(x, dt, A, B, C, D, chunk: int):
    """``(y [b, L, E] in x.dtype, states [b, chunks, E, N] float32: the state
    that enters each chunk)``."""
    xf, dtf, Af, Bf, Cf, Df = _f32(x, dt, A, B, C, D)

    def step(state, rows):
        after, y = _chunk(state, *rows[:2], Af, *rows[2:], Df)
        return after, (state, y)

    zero = jnp.zeros((x.shape[0],) + A.shape, jnp.float32)
    _, (states, y) = jax.lax.scan(
        step, zero, tuple(_chunked(t, chunk) for t in (xf, dtf, Bf, Cf)))
    y = jnp.moveaxis(y, 0, 1).reshape(x.shape)
    return y.astype(x.dtype), jnp.moveaxis(states, 0, 1)


def _xla_backward(x, dt, A, B, C, D, states, dy, chunk: int):
    xf, dtf, Af, Bf, Cf, Df, dyf = _f32(x, dt, A, B, C, D, dy)

    def step(carry, rows):
        d_state, dA, dD = carry
        state, x_c, dt_c, b_c, c_c, dy_c = rows
        _, transpose = jax.vjp(_chunk, state, x_c, dt_c, Af, b_c, c_c, Df)
        d_state, dx, ddt, dA_c, dB, dC, dD_c = transpose((d_state, dy_c))
        return (d_state, dA + dA_c, dD + dD_c), (dx, ddt, dB, dC)

    zero = jnp.zeros((x.shape[0],) + A.shape, jnp.float32)
    rows = (jnp.moveaxis(states, 1, 0),) + tuple(
        _chunked(t, chunk) for t in (xf, dtf, Bf, Cf, dyf))
    (_, dA, dD), parts = jax.lax.scan(
        step, (zero, jnp.zeros_like(Af), jnp.zeros_like(Df)), rows, reverse=True)
    dx, ddt, dB, dC = (jnp.moveaxis(p, 0, 1).reshape(like.shape).astype(like.dtype)
                       for p, like in zip(parts, (x, dt, B, C)))
    return dx, ddt, dA.astype(A.dtype), dB, dC, dD.astype(D.dtype)


# ----------------------------------------------------------------- kernels

def _row(ref, t):
    """Token ``t`` of a float32 ``[chunk, 1,024]`` block as one register,
    ``[8, 128]`` with channel ``128 j + lane`` on sublane ``j``. In VMEM the
    row is one sublane of eight neighbouring tiles, so this is a load with a
    sublane stride of 8 and no arithmetic: the kernel reads the rows where
    the projections wrote them."""
    return ref[pl.ds(t, 1), :].reshape(8, _LANES)


def _put_row(ref, t, register):
    """The store that mirrors :func:`_row`."""
    ref[pl.ds(t, 1), :] = register.reshape(1, _TILE)


def _float32_rows(ref, scratch_ref, fill: bool = True):
    """The block the token loop reads (or, ``fill=False``, writes) its rows
    in: ``ref`` itself, or, of a packed dtype (a bfloat16 tile holds 16
    tokens, two a sublane: no row of it can be loaded or stored alone),
    ``scratch_ref``, float32, filled with ``ref``'s values."""
    if ref.dtype == jnp.float32:
        return ref
    if fill:
        scratch_ref[...] = ref[...].astype(jnp.float32)
    return scratch_ref


def _fwd_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, d_ref, y_ref, states_ref,
                state_ref, xs_ref, ys_ref, *, n: int, chunk: int):
    @pl.when(pl.program_id(2) == 0)
    def _first_chunk_of_a_sequence():
        state_ref[...] = jnp.zeros_like(state_ref)

    states_ref[...] = state_ref[...]
    d = d_ref[...]
    xs_ref = _float32_rows(x_ref, xs_ref)
    ys_ref = _float32_rows(y_ref, ys_ref, fill=False)

    def token(t, state):
        x = _row(xs_ref, t)
        dt = _row(dt_ref, t)
        dtx = dt * x
        y = d * x
        after = []
        for i in range(n):
            s = jnp.exp2(dt * a_ref[i]) * state[i] + dtx * b_ref[t * n + i]
            y = y + s * c_ref[t * n + i]
            after.append(s)
        _put_row(ys_ref, t, y)
        return tuple(after)

    state = jax.lax.fori_loop(0, chunk, token,
                              tuple(state_ref[i] for i in range(n)))
    for i in range(n):
        state_ref[i] = state[i]
    if ys_ref is not y_ref:
        y_ref[...] = ys_ref[...].astype(y_ref.dtype)


def _bwd_kernel(b_ref, c_ref, x_ref, dt_ref, dy_ref, a_ref, d_ref, states_ref,
                dx_ref, ddt_ref, da_ref, dd_ref, db_ref, dc_ref,
                before_ref, d_state_ref, pb_ref, pc_ref, xs_ref, dys_ref,
                dxs_ref, *, n: int, chunk: int, tiles: int):
    first_chunk = pl.program_id(1) == 0          # the sequence's last
    j = pl.program_id(2)
    rows = pl.ds(pl.multiple_of(j * 8, 8), 8)

    @pl.when(first_chunk)
    def _last_chunk_of_a_sequence():
        d_state_ref[j] = jnp.zeros_like(d_state_ref[j])
        da_ref[:, rows, :] = jnp.zeros((n, 8, _LANES), jnp.float32)
        dd_ref[rows, :] = jnp.zeros((8, _LANES), jnp.float32)

    @pl.when(j == 0)
    def _first_tile_of_a_chunk():
        pb_ref[...] = jnp.zeros_like(pb_ref)
        pc_ref[...] = jnp.zeros_like(pc_ref)

    xs_ref = _float32_rows(x_ref, xs_ref)
    dys_ref = _float32_rows(dy_ref, dys_ref)
    dxs_ref = _float32_rows(dx_ref, dxs_ref, fill=False)

    # the chunk's states again, from the one that entered it: before_ref[t]
    # is the state token t finds, before_ref[t + 1] the one it leaves
    def forward(t, state):
        dt = _row(dt_ref, t)
        dtx = dt * _row(xs_ref, t)
        after = []
        for i in range(n):
            before_ref[t, i] = state[i]
            after.append(jnp.exp2(dt * a_ref[i]) * state[i]
                         + dtx * b_ref[t * n + i])
        return tuple(after)

    last = jax.lax.fori_loop(0, chunk, forward,
                             tuple(states_ref[i] for i in range(n)))
    for i in range(n):
        before_ref[chunk, i] = last[i]
    d = d_ref[...]
    zero = jnp.zeros((8, _LANES), jnp.float32)

    def backward(step, carry):
        d_state, d_a, d_d = carry
        t = chunk - 1 - step
        x = _row(xs_ref, t)
        dt = _row(dt_ref, t)
        dy = _row(dys_ref, t)
        dtx = dt * x
        d_dtx, d_dt = zero, zero
        next_state, next_a = [], []
        for i in range(n):
            a = a_ref[i]
            through = d_state[i] + dy * c_ref[t * n + i]      # ds_t
            pc_ref[t, i] += before_ref[t + 1, i] * dy
            pb_ref[t, i] += through * dtx
            d_dtx = d_dtx + through * b_ref[t * n + i]
            through = through * jnp.exp2(dt * a)              # ds_{t-1}
            moved = through * before_ref[t, i]                # d(dt A) here
            d_dt = d_dt + moved * a
            next_a.append(d_a[i] + moved * dt)
            next_state.append(through)
        _put_row(dxs_ref, t, d_dtx * dt + d * dy)
        _put_row(ddt_ref, t, d_dt * _LN2 + d_dtx * x)
        return tuple(next_state), tuple(next_a), d_d + dy * x

    d_state, d_a, d_d = jax.lax.fori_loop(
        0, chunk, backward,
        (tuple(d_state_ref[j, i] for i in range(n)), (zero,) * n, zero))
    for i in range(n):
        d_state_ref[j, i] = d_state[i]
    da_ref[:, rows, :] += jnp.stack(d_a)
    dd_ref[rows, :] += d_d
    if dxs_ref is not dx_ref:
        dx_ref[...] = dxs_ref[...].astype(dx_ref.dtype)

    @pl.when(j == tiles - 1)
    def _last_tile_of_a_chunk():
        ones = jnp.ones((8, _LANES), jnp.float32)
        per = _LANES // n                        # tokens whose sums fill 128 lanes

        def reduce(block, _):
            tokens = pl.ds(pl.multiple_of(block * per, per), per)
            lanes = pl.ds(pl.multiple_of(block * _LANES, _LANES), _LANES)
            for part_ref, out_ref in ((pb_ref, db_ref), (pc_ref, dc_ref)):
                sums = jnp.sum(part_ref[tokens], axis=2).reshape(_LANES, _LANES)
                # [8, 128]: every row the sums over the lanes, (t, n) on the
                # lanes in row-major order, as XLA reads a [Q, N] block
                out_ref[:, lanes] = jax.lax.dot_general(
                    ones, sums, (((1,), (1,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
            return _

        jax.lax.fori_loop(0, chunk * n // _LANES, reduce, 0)


# ------------------------------------------------------------------- calls

def _sizes(x, A):
    b, length, e = x.shape
    return b, length, e, A.shape[1]


def _layouts(x, dt, A, B, C, D):
    """The arrays as the kernels read them: ``x`` and ``dt`` the ``[B, L,
    E]`` rows they are (:func:`_row`), ``A / ln 2`` a state index major with
    the channels as ``[E / 128, 128]`` (the decay is ``exp2`` of its product
    with ``dt``: the EUP's own power, one multiply less an element), ``D``
    likewise, ``B`` and ``C`` float32 and flat (scalars in SMEM, ``[t * N +
    n]``)."""
    b, length, e, n = _sizes(x, A)
    flat = lambda t: t.astype(jnp.float32).reshape(b, length * n)  # noqa: E731
    return (flat(B), flat(C), x, dt.astype(jnp.float32),
            (A.astype(jnp.float32) / _LN2).T.reshape(n, e // _LANES, _LANES),
            D.astype(jnp.float32).reshape(e // _LANES, _LANES))


def _forward_call(x, dt, A, B, C, D, chunk: int, interpret: bool):
    b, length, e, n = _sizes(x, A)
    nc, tiles = length // chunk, e // _TILE
    scalars = pl.BlockSpec((None, chunk * n), lambda s, j, c: (s, c),
                           memory_space=pltpu.SMEM)
    wide = pl.BlockSpec((None, chunk, _TILE), lambda s, j, c: (s, c, j))
    y, states = named_pallas_call(
        "selective_scan_fwd", functools.partial(_fwd_kernel, n=n, chunk=chunk),
        grid=(b, tiles, nc),
        in_specs=[scalars, scalars, wide, wide,
                  pl.BlockSpec((n, 8, _LANES), lambda s, j, c: (0, j, 0)),
                  pl.BlockSpec((8, _LANES), lambda s, j, c: (j, 0))],
        out_specs=[wide,
                   pl.BlockSpec((None, None, n, 8, _LANES),
                                lambda s, j, c: (s, c, 0, j, 0))],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((b, nc, n, e // _LANES, _LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, 8, _LANES), jnp.float32)]
        + [pltpu.VMEM((chunk, _TILE), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*_layouts(x, dt, A, B, C, D))
    # [b, chunks, N, E] -> [b, chunks, E, N], as the plain path keeps them
    return y, jnp.swapaxes(states.reshape(b, nc, n, e), 2, 3)


def _backward_call(x, dt, A, B, C, D, states, dy, chunk: int, interpret: bool):
    b, length, e, n = _sizes(x, A)
    nc, tiles = length // chunk, e // _TILE
    back = lambda c: nc - 1 - c  # noqa: E731
    scalars = pl.BlockSpec((None, chunk * n), lambda s, c, j: (s, back(c)),
                           memory_space=pltpu.SMEM)
    wide = pl.BlockSpec((None, chunk, _TILE), lambda s, c, j: (s, back(c), j))
    sums = pl.BlockSpec((None, None, 8, chunk * n),
                        lambda s, c, j: (s, back(c), 0, 0))
    b2, c2, _, dt32, a3, d2 = _layouts(x, dt, A, B, C, D)
    dx, ddt, dA, dD, dB, dC = named_pallas_call(
        "selective_scan_bwd",
        functools.partial(_bwd_kernel, n=n, chunk=chunk, tiles=tiles),
        grid=(b, nc, tiles),
        in_specs=[scalars, scalars, wide, wide, wide,
                  pl.BlockSpec((n, 8, _LANES), lambda s, c, j: (0, j, 0)),
                  pl.BlockSpec((8, _LANES), lambda s, c, j: (j, 0)),
                  pl.BlockSpec((None, None, n, 8, _LANES),
                               lambda s, c, j: (s, back(c), 0, j, 0))],
        out_specs=[wide, wide,
                   pl.BlockSpec((None, n, e // _LANES, _LANES),
                                lambda s, c, j: (s, 0, 0, 0)),
                   pl.BlockSpec((None, e // _LANES, _LANES),
                                lambda s, c, j: (s, 0, 0)),
                   sums, sums],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, n, e // _LANES, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((b, e // _LANES, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((b, nc, 8, chunk * n), jnp.float32),
                   jax.ShapeDtypeStruct((b, nc, 8, chunk * n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((chunk + 1, n, 8, _LANES), jnp.float32),
                        pltpu.VMEM((tiles, n, 8, _LANES), jnp.float32),
                        pltpu.VMEM((chunk, n, 8, _LANES), jnp.float32),
                        pltpu.VMEM((chunk, n, 8, _LANES), jnp.float32)]
        + [pltpu.VMEM((chunk, _TILE), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(b2, c2, x, dt32, dy.astype(x.dtype), a3, d2,
      jnp.swapaxes(states, 2, 3).reshape(b, nc, n, e // _LANES, _LANES))
    small = lambda t, like: t[:, :, 0].reshape(like.shape).astype(like.dtype)  # noqa: E731
    return (dx, ddt.astype(dt.dtype),
            jnp.sum(dA, axis=0).reshape(n, e).T.astype(A.dtype),
            small(dB, B), small(dC, C),
            jnp.sum(dD, axis=0).reshape(e).astype(D.dtype))


# --------------------------------------------------------------- public op

def moved_bytes(x, A, chunk: int):
    """``(forward, backward)`` bytes one call must move, each operand once:
    forward ``x`` and ``dt`` read, ``y`` and one float32 ``[E, N]`` state a
    chunk written; backward ``x``, ``dt``, ``dy`` and the states read, ``dx``
    and ``ddt`` written. ``B``, ``C`` (``[b, L, N]``) and their gradients are
    nothing beside them and left out."""
    b, length, e, n = _sizes(x, A)
    size = jnp.dtype(x.dtype).itemsize
    act, f32 = b * length * e * size, b * length * e * 4
    states = b * -(-length // chunk) * e * n * 4
    return (2 * act + f32 + states, 3 * act + 2 * f32 + states)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, dt, A, B, C, D, chunk, impl):
    return _scan_fwd(x, dt, A, B, C, D, chunk, impl)[0]


def _scan_fwd(x, dt, A, B, C, D, chunk, impl):
    if impl == "xla":
        y, states = _xla_forward(x, dt, A, B, C, D, chunk)
    else:
        y, states = _forward_call(x, dt, A, B, C, D, chunk,
                                  _flash._use_interpret())
    return y, (x, dt, A, B, C, D, states)


def _scan_bwd(chunk, impl, residuals, dy):
    if impl == "xla":
        return _xla_backward(*residuals, dy, chunk)
    return _backward_call(*residuals, dy, chunk, _flash._use_interpret())


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                   C: jax.Array, D: jax.Array, chunk: int = DEFAULT_CHUNK,
                   impl: str = "xla") -> jax.Array:
    """``y_t = s_t C_t + D x_t`` with ``s_t = exp(dt_t A) s_{t-1} + dt_t x_t
    B_t^T`` (module docstring). x, dt: ``[batch, L, E]`` (``dt`` positive:
    after its softplus); A: ``[E, N]``; B, C: ``[batch, L, N]``; D: ``[E]``;
    ``impl``: ``"xla"`` or ``"pallas"`` (``E`` a multiple of 1,024, ``N`` a divisor of
    128). Returns ``[batch, L, E]`` in ``x.dtype``; the arithmetic is
    float32. Differentiable in all six; the inputs and one float32 ``[E, N]``
    state a chunk are kept for the backward, never a state a token. A length
    that is not a multiple of ``chunk`` is padded with ``dt = 0`` rows (no
    decay, nothing added), whose outputs are dropped.

    Under a mesh of several devices the kernels run per device
    (:func:`autodist_tpu.parallel.mesh.per_device`), the batch split over the
    data axes."""
    if impl not in IMPLS:
        raise ValueError(f"Unknown selective scan impl {impl!r}; valid: {IMPLS}")
    b, length, e = x.shape
    if (dt.shape != x.shape or A.ndim != 2 or A.shape[0] != e
            or D.shape != (e,) or B.shape != C.shape
            or B.shape != (b, length, A.shape[1])):
        raise ValueError(
            f"selective_scan: x {x.shape}, dt {dt.shape}, A {A.shape}, B "
            f"{B.shape}, C {C.shape}, D {D.shape}; want [B, L, E] twice, "
            f"[E, N], [B, L, N] twice, [E]")
    n = A.shape[1]
    # a chunk: whole bfloat16 tiles of 16 rows, dB / dC's sums whole lane tiles
    whole = max(16, 8 * _LANES // n)
    if impl == "pallas" and (e % _TILE or _LANES % n or chunk % whole):
        raise ValueError(
            f"selective_scan kernels: {e} channels must be a multiple of "
            f"{_TILE}, state {n} a divisor of {_LANES}, chunk {chunk} a "
            f"multiple of {whole}")
    chunks = -(-length // chunk)
    telemetry.counter("selective_scan.calls").inc()
    telemetry.gauge("selective_scan.chunk").set(chunk)
    telemetry.gauge("selective_scan.chunks").set(b * chunks)
    telemetry.gauge("selective_scan.channels").set(e)
    telemetry.gauge("selective_scan.state").set(n)
    fwd_bytes, bwd_bytes = moved_bytes(x, A, chunk)
    telemetry.gauge("selective_scan.fwd.bytes").set(fwd_bytes)
    telemetry.gauge("selective_scan.bwd.bytes").set(bwd_bytes)
    pad = chunks * chunk - length
    if pad:
        rows = lambda t: jnp.pad(t, [(0, 0), (0, pad), (0, 0)])  # noqa: E731
        x, dt, B, C = rows(x), rows(dt), rows(B), rows(C)
    run = functools.partial(_scan, chunk=chunk, impl=impl)
    if impl == "xla":
        y = run(x, dt, A, B, C, D)
    else:
        from autodist_tpu.parallel.mesh import per_device
        y = per_device(run, (x, dt, A, B, C, D),
                       batched=(True, True, False, True, True, False))
    return y[:, :length] if pad else y
