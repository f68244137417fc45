"""Gated short causal convolution — the token mixer of the conv-attention
hybrids (LFM2), plain and as two pallas TPU kernels.

``gated_short_conv(bcu [B, L, 3d], w [d, K]) -> [B, L, d]``: with ``[B | C | u]``
the three thirds of ``bcu`` along its last axis (the input projection's
result, in this order)::

    v_t = B_t * u_t
    c_t = sum_{j=0..K-1} w[:, j] * v_{t-(K-1)+j}        v_s = 0 for s < 0
    y_t = C_t * c_t

depthwise over the ``d`` channels, causal, each sequence of the batch on its
own: position 0 of every sequence sees zeros before it. No activation, no
bias; the arithmetic is float32 on the operands as they come (bfloat16 in the
models), the result is cast back to ``bcu.dtype``.

There are no matrix products here: the operator moves ``8.T.d`` bytes forward
(three thirds read, ``y`` written, two bytes an element) and ``14.T.d``
backward (``bcu`` and ``dy`` read, ``dbcu`` written), and nothing else of it
costs anything a chip would notice. So the kernels exist to move every
operand once:

- ``short_conv_fwd``: grid (sequence, row block). Three block specs on the
  one array read the thirds in place (no split copies); a row block walks
  its rows 16 at a time with the 16 rows of ``v`` before them at hand (the
  walk's carry), and the last 16 of the block wait in VMEM for the sequence's
  next row block (zeros at a sequence's first), so the ``K - 1`` rows before
  a block are never read twice.
- ``short_conv_bwd``: the same walk recomputes ``v`` and ``c`` and gives
  ``dC = dy * c``, ``dv_s = sum_j w_j * g_{s+(K-1)-j}`` with ``g = dy * C``,
  ``dB = dv * u``, ``du = dv * B``, written as the three thirds of one
  ``dbcu`` block, and ``dw_j = sum_t g_t * v_{t-(K-1)+j}`` summed in float32
  across every row block of the call in an output block that stays in VMEM.
  ``dv`` needs the ``K - 1`` rows of ``g`` *after* a block: two 16-row block
  specs (the ``C`` third and ``dy``) bring them, zero past a sequence's end;
  they are the only bytes read twice, 2 x 16 rows in 7 x ``block_rows``.

The custom VJP saves ``bcu`` and ``w`` and nothing else, on both paths
(``impl="xla"``: the same equations in ``jax.numpy``, transposed by autodiff
from the saved operands; init, the CPU and the comparison run it). On the CPU
backend the kernels run in pallas interpret mode; ``tests/test_chip_compile.py``
compiles them for a described v5e at the LFM2 cell's shape.

Beside it the ungated form the state-space hybrids run before their scan
(Mamba-2's and Mamba-1's): :func:`conv_silu`, ``silu(causal_conv_w(x) + b)``,
plain and as two kernels of the same walk, ``conv_silu_fwd`` and
``conv_silu_bwd`` (grid (channel block, sequence, row block)); its custom VJP
keeps ``x``, ``w`` and ``b`` and nothing else, as the gated form's does. Its
operand may be a column window of a wider array (``at``: Nemotron's
``in_proj`` output ``[z | xBC | dt]`` handed whole, of which the kernels read
channel blocks ``at / block`` onward where they lie): the caller's slice was
a pass over memory of its own, 100 MB a call (PERF.md section 6, "PR 49").
An operand as wide as the taps traces what it always traced.
"""

import functools
import importlib
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu import telemetry
from autodist_tpu.ops.named_call import named_pallas_call

# The module, as ``grouped_matmul`` reads it: a compile rehearsal that steers
# the flash kernels to compile steers these too.
_flash = importlib.import_module("autodist_tpu.ops.flash_attention")

IMPLS = ("xla", "pallas")
_SUB = 16               # rows a walk step takes: one packed bfloat16 tile
# Rows a grid step holds, and channels a walk works on at a time (the walk's
# arrays are [16, channels] float32, ``channels / 64`` vector registers each).
# Stand-alone on a TPU v5e at [2, 8192, 6144] (tools/short_conv_timing.py,
# PERF.md §6 "PR 31") rows 128-512 x channels 256-1,024 all read 0.388-0.393 ms
# forward and 0.716-0.732 backward, 84% and 80% of what 819 GB/s allows: the
# tiles do not decide; 1,024 rows backward do not fit the limit below.
FWD_BLOCK_ROWS = 256
BWD_BLOCK_ROWS = 256
_CHANNELS = 512
# The backward's seven blocks of block_rows x d, double-buffered, are 14 MiB
# at 256 x 2,048 in bfloat16, 28 in float32: past Mosaic's default 16 MiB.
_VMEM_LIMIT = 48 << 20


def _plain(bcu, w):
    """The equations as they stand, float32, three shifted products on a
    zero-padded array."""
    _, k = w.shape
    length = bcu.shape[1]
    b, c, u = jnp.split(bcu.astype(jnp.float32), 3, axis=-1)
    v = jnp.pad(b * u, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(w[:, j].astype(jnp.float32) * v[:, j:j + length]
               for j in range(k))
    return (c * conv).astype(bcu.dtype)


def _plain_silu(x, w, b):
    """``silu(causal_conv_w(x) + b)``, float32, ``K`` shifted products on a
    zero-padded array."""
    _, k = w.shape
    length = x.shape[1]
    v = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(w[:, j].astype(jnp.float32) * v[:, j:j + length]
               for j in range(k))
    return jax.nn.silu(conv + b.astype(jnp.float32)).astype(x.dtype)


# ----------------------------------------------------------------- kernels

def _shifted(rows, before, s: int, row):
    """``rows`` moved down by ``s``: row ``t`` holds ``rows[t - s]``, and for
    ``t < s`` the end of the 16 rows ``before`` them."""
    return jnp.where(row < s, pltpu.roll(before, s, 0), pltpu.roll(rows, s, 0))


def _shifted_up(rows, after, s: int, row):
    """``rows`` moved up by ``s``: row ``t`` holds ``rows[t + s]``, and for
    ``t >= 16 - s`` the start of the 16 rows ``after`` them."""
    return jnp.where(row >= _SUB - s, pltpu.roll(after, _SUB - s, 0),
                     pltpu.roll(rows, _SUB - s, 0))


def _f32(ref, rows, lanes):
    return ref[0, rows, lanes].astype(jnp.float32)


def _fwd_kernel(b_ref, c_ref, u_ref, w_ref, o_ref, carry_ref, *, k: int,
                channels: int):
    _, block_rows, d = o_ref.shape

    @pl.when(pl.program_id(1) == 0)
    def _first_block_of_a_sequence():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    row = jax.lax.broadcasted_iota(jnp.int32, (_SUB, channels), 0)
    for first in range(0, d, channels):
        lanes = pl.ds(first, channels)
        taps = [w_ref[j:j + 1, lanes] for j in range(k)]

        def walk(r, before, lanes=lanes, taps=taps):
            rows = pl.ds(pl.multiple_of(r * _SUB, _SUB), _SUB)
            v = _f32(b_ref, rows, lanes) * _f32(u_ref, rows, lanes)
            conv = taps[k - 1] * v
            for s in range(1, k):
                conv += taps[k - 1 - s] * _shifted(v, before, s, row)
            o_ref[0, rows, lanes] = (_f32(c_ref, rows, lanes)
                                     * conv).astype(o_ref.dtype)
            return v

        carry_ref[:, lanes] = jax.lax.fori_loop(
            0, block_rows // _SUB, walk, carry_ref[:, lanes])


def _bwd_kernel(b_ref, c_ref, u_ref, dy_ref, c_after_ref, dy_after_ref, w_ref,
                dbcu_ref, dw_ref, carry_ref, *, k: int, channels: int,
                length: int):
    _, block_rows, d = dy_ref.shape
    i = pl.program_id(1)
    steps = block_rows // _SUB
    ragged = length % block_rows != 0

    @pl.when((pl.program_id(0) == 0) & (i == 0))
    def _first_block_of_the_call():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(i == 0)
    def _first_block_of_a_sequence():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    row = jax.lax.broadcasted_iota(jnp.int32, (_SUB, channels), 0)

    def inside(first_row):
        """Rows of the 16 from ``first_row`` (of this block) that the
        sequence has."""
        return i * block_rows + first_row + row < length

    for first in range(0, d, channels):
        lanes = pl.ds(first, channels)
        taps = [w_ref[j:j + 1, lanes] for j in range(k)]
        # g of the 16 rows after this block: zero past the sequence's end
        # (the last block's spec is clamped onto rows that are not after it)
        g_after = jnp.where(
            inside(block_rows),
            _f32(c_after_ref, slice(None), lanes)
            * _f32(dy_after_ref, slice(None), lanes), 0.0)

        def walk(r, carry, lanes=lanes, taps=taps, g_after=g_after):
            before, sums = carry
            rows = pl.ds(pl.multiple_of(r * _SUB, _SUB), _SUB)
            b, u = _f32(b_ref, rows, lanes), _f32(u_ref, rows, lanes)
            dy = _f32(dy_ref, rows, lanes)
            v, g = b * u, dy * _f32(c_ref, rows, lanes)
            nxt = jnp.minimum(r + 1, steps - 1) * _SUB
            rows_after = pl.ds(pl.multiple_of(nxt, _SUB), _SUB)
            after = _f32(c_ref, rows_after, lanes) * _f32(dy_ref, rows_after,
                                                          lanes)
            if ragged:
                # the last block's rows past the sequence hold anything: they
                # must not reach dv (through g) or dw (through g and v)
                valid = inside(r * _SUB)
                v, g = jnp.where(valid, v, 0.0), jnp.where(valid, g, 0.0)
                after = jnp.where(inside(nxt), after, 0.0)
            after = jnp.where(r == steps - 1, g_after, after)
            conv, dv = taps[k - 1] * v, taps[k - 1] * g
            sums = list(sums)
            sums[k - 1] += g * v
            for s in range(1, k):
                moved = _shifted(v, before, s, row)
                conv += taps[k - 1 - s] * moved
                sums[k - 1 - s] += g * moved
                dv += taps[k - 1 - s] * _shifted_up(g, after, s, row)
            out = dbcu_ref.dtype
            dbcu_ref[0, rows, lanes] = (dv * u).astype(out)
            dbcu_ref[0, rows, pl.ds(d + first, channels)] = (dy * conv).astype(out)
            dbcu_ref[0, rows, pl.ds(2 * d + first, channels)] = (dv * b).astype(out)
            return v, tuple(sums)

        zeros = jnp.zeros((_SUB, channels), jnp.float32)
        carry_ref[:, lanes], sums = jax.lax.fori_loop(
            0, steps, walk, (carry_ref[:, lanes], (zeros,) * k))
        for j in range(k):
            dw_ref[j:j + 1, lanes] += jnp.sum(sums[j], axis=0, keepdims=True)


def _silu_fwd_kernel(x_ref, w_ref, bias_ref, o_ref, carry_ref, *, k: int,
                     channels: int):
    """``silu(conv + b)`` of one (channel block, sequence, row block): the
    gated forward's walk with ``v = x`` and the bias and SiLU where it has the
    ``C`` gate."""
    _, block_rows, d = o_ref.shape

    @pl.when(pl.program_id(2) == 0)
    def _first_block_of_a_sequence():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    row = jax.lax.broadcasted_iota(jnp.int32, (_SUB, channels), 0)
    for first in range(0, d, channels):
        lanes = pl.ds(first, channels)
        taps = [w_ref[j:j + 1, lanes] for j in range(k)]
        bias = bias_ref[:, lanes]

        def walk(r, before, lanes=lanes, taps=taps, bias=bias):
            rows = pl.ds(pl.multiple_of(r * _SUB, _SUB), _SUB)
            v = _f32(x_ref, rows, lanes)
            conv = bias + taps[k - 1] * v
            for s in range(1, k):
                conv += taps[k - 1 - s] * _shifted(v, before, s, row)
            o_ref[0, rows, lanes] = (conv * jax.nn.sigmoid(conv)).astype(o_ref.dtype)
            return v

        carry_ref[:, lanes] = jax.lax.fori_loop(
            0, block_rows // _SUB, walk, carry_ref[:, lanes])


def _silu_bwd_kernel(x_ref, dy_ref, x_after_ref, dy_after_ref, w_ref, bias_ref,
                     dx_ref, dw_ref, db_ref, carry_ref, *, k: int, channels: int,
                     length: int):
    """``g = dy * silu'(conv + b)`` needs the convolution again, so the walk
    is one step ahead of what it writes: step ``r`` computes ``g`` of its 16
    rows (and their part of ``dw`` and ``db``) and then ``dx`` of the 16 rows
    *before* them, which need the ``K - 1`` rows of ``g`` after; the block's
    last 16 rows take theirs from the 16 rows after the block (two 16-row
    block specs, zero past a sequence's end)."""
    _, block_rows, d = dy_ref.shape
    i = pl.program_id(2)
    steps = block_rows // _SUB
    ragged = length % block_rows != 0

    @pl.when((pl.program_id(1) == 0) & (i == 0))
    def _first_block_of_the_channels():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    @pl.when(i == 0)
    def _first_block_of_a_sequence():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    row = jax.lax.broadcasted_iota(jnp.int32, (_SUB, channels), 0)

    def inside(first_row):
        return i * block_rows + first_row + row < length

    for first in range(0, d, channels):
        lanes = pl.ds(first, channels)
        taps = [w_ref[j:j + 1, lanes] for j in range(k)]
        bias = bias_ref[:, lanes]

        def g_of(v, before, dy, taps=taps, bias=bias):
            """``(g, [v moved down by 1..K-1])`` of 16 rows."""
            moved = [_shifted(v, before, s, row) for s in range(1, k)]
            pre = bias + taps[k - 1] * v
            for s in range(1, k):
                pre += taps[k - 1 - s] * moved[s - 1]
            sig = jax.nn.sigmoid(pre)
            return dy * sig * (1.0 + pre * (1.0 - sig)), moved

        def dx_of(g, after, taps=taps):
            dv = taps[k - 1] * g
            for s in range(1, k):
                dv += taps[k - 1 - s] * _shifted_up(g, after, s, row)
            return dv

        def rows_of(r, lanes=lanes):
            rows = pl.ds(pl.multiple_of(r * _SUB, _SUB), _SUB)
            v, dy = _f32(x_ref, rows, lanes), _f32(dy_ref, rows, lanes)
            if ragged:      # rows past the sequence hold anything
                valid = inside(r * _SUB)
                v, dy = jnp.where(valid, v, 0.0), jnp.where(valid, dy, 0.0)
            return v, dy

        def summed(sums, g, v, moved):
            sums = list(sums)
            sums[k - 1] += g * v
            for s in range(1, k):
                sums[k - 1 - s] += g * moved[s - 1]
            sums[k] += g
            return tuple(sums)

        def walk(r, carry, lanes=lanes):
            before, g_before, sums = carry
            v, dy = rows_of(r)
            g, moved = g_of(v, before, dy)
            rows = pl.ds(pl.multiple_of((r - 1) * _SUB, _SUB), _SUB)
            dx_ref[0, rows, lanes] = dx_of(g_before, g).astype(dx_ref.dtype)
            return v, g, summed(sums, g, v, moved)

        zeros = jnp.zeros((_SUB, channels), jnp.float32)
        v, dy = rows_of(0)
        g, moved = g_of(v, carry_ref[:, lanes], dy)
        v, g, sums = jax.lax.fori_loop(
            1, steps, walk, (v, g, summed((zeros,) * (k + 1), g, v, moved)))
        g_after, _ = g_of(_f32(x_after_ref, slice(None), lanes), v,
                          _f32(dy_after_ref, slice(None), lanes))
        g_after = jnp.where(inside(block_rows), g_after, 0.0)
        last = pl.ds((steps - 1) * _SUB, _SUB)
        dx_ref[0, last, lanes] = dx_of(g, g_after).astype(dx_ref.dtype)
        carry_ref[:, lanes] = v
        for j in range(k):
            dw_ref[j:j + 1, lanes] += jnp.sum(sums[j], axis=0, keepdims=True)
        db_ref[:, lanes] += jnp.sum(sums[k], axis=0, keepdims=True)


# ------------------------------------------------------------------- calls

def _check(bcu, w):
    if bcu.ndim != 3 or w.ndim != 2 or bcu.shape[2] != 3 * w.shape[0]:
        raise ValueError(f"gated_short_conv: bcu {bcu.shape} against taps "
                         f"{w.shape}; want [B, L, 3d] and [d, K]")
    d, k = w.shape
    if d % 128 or not 1 <= k <= _SUB + 1:
        raise ValueError(f"gated_short_conv kernels: d {d} must be a multiple "
                         f"of 128 and K {k} at most {_SUB + 1}")


def _tiles(length: int, d: int, block_rows: int, channels: int):
    block_rows = min(block_rows, -(-length // _SUB) * _SUB)
    if block_rows % _SUB:
        raise ValueError(f"block_rows {block_rows} is not a multiple of {_SUB}")
    channels = min(channels, d)
    while d % channels:
        channels -= 128
    return block_rows, channels


def _spec_bytes(grid_steps: int, blocks, whole) -> int:
    """What one call moves by its block specs: every stepped block once a
    grid step, every resident one once."""
    count = lambda shape, dtype: math.prod(shape) * jnp.dtype(dtype).itemsize  # noqa: E731
    return (grid_steps * sum(count(*b) for b in blocks)
            + sum(count(*w) for w in whole))


def _forward_call(bcu, w, interpret: bool, block_rows=None, channels=None):
    _check(bcu, w)
    batch, length, _ = bcu.shape
    d, k = w.shape
    block_rows, channels = _tiles(length, d, block_rows or FWD_BLOCK_ROWS,
                                  channels or _CHANNELS)
    n_blocks = pl.cdiv(length, block_rows)
    block = (1, block_rows, d)
    telemetry.gauge("short_conv.fwd.block_rows").set(block_rows)
    telemetry.gauge("short_conv.fwd.bytes").set(_spec_bytes(
        batch * n_blocks, [(block, bcu.dtype)] * 4, [((k, d), jnp.float32)]))
    third = lambda n: pl.BlockSpec(block, lambda b, i: (b, i, n))  # noqa: E731
    return named_pallas_call(
        "short_conv_fwd",
        functools.partial(_fwd_kernel, k=k, channels=channels),
        grid=(batch, n_blocks),
        in_specs=[third(0), third(1), third(2),
                  pl.BlockSpec((k, d), lambda b, i: (0, 0))],
        out_specs=pl.BlockSpec(block, lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, length, d), bcu.dtype),
        scratch_shapes=[pltpu.VMEM((_SUB, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(bcu, bcu, bcu, w.astype(jnp.float32).T)


def _backward_call(bcu, w, dy, interpret: bool, block_rows=None, channels=None):
    _check(bcu, w)
    batch, length, _ = bcu.shape
    d, k = w.shape
    block_rows, channels = _tiles(length, d, block_rows or BWD_BLOCK_ROWS,
                                  channels or _CHANNELS)
    n_blocks = pl.cdiv(length, block_rows)
    block, halo = (1, block_rows, d), (1, _SUB, d)
    telemetry.gauge("short_conv.bwd.block_rows").set(block_rows)
    telemetry.gauge("short_conv.bwd.bytes").set(_spec_bytes(
        batch * n_blocks,
        [(block, bcu.dtype)] * 4 + [(halo, bcu.dtype)] * 2
        + [((1, block_rows, 3 * d), bcu.dtype)],
        [((k, d), jnp.float32)] * 2))
    last_halo = pl.cdiv(length, _SUB) - 1
    per_block = block_rows // _SUB
    third = lambda n: pl.BlockSpec(block, lambda b, i: (b, i, n))  # noqa: E731
    after = lambda n: pl.BlockSpec(  # noqa: E731 — the 16 rows after block i
        halo, lambda b, i: (b, jnp.minimum((i + 1) * per_block, last_halo), n))
    dy = dy.astype(bcu.dtype)
    dbcu, dw = named_pallas_call(
        "short_conv_bwd",
        functools.partial(_bwd_kernel, k=k, channels=channels, length=length),
        grid=(batch, n_blocks),
        in_specs=[third(0), third(1), third(2), third(0), after(1), after(0),
                  pl.BlockSpec((k, d), lambda b, i: (0, 0))],
        out_specs=[pl.BlockSpec((1, block_rows, 3 * d), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((k, d), lambda b, i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct(bcu.shape, bcu.dtype),
                   jax.ShapeDtypeStruct((k, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_SUB, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # dw is one block summed over the whole grid
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(bcu, bcu, bcu, dy, bcu, dy, w.astype(jnp.float32).T)
    return dbcu, dw.T.astype(w.dtype)


_SILU_BLOCK_D = 2048    # channels a grid step holds: 6,144 = 3 x 2,048


def _silu_tiles(x, w, block_rows: int, at: int = 0):
    if x.ndim != 3 or w.ndim != 2 or x.shape[2] < at + w.shape[0]:
        raise ValueError(f"conv_silu: x {x.shape} against taps {w.shape}; "
                         f"want [B, L, d] and [d, K]")
    d, k = w.shape
    if d % 128 or not 1 <= k <= _SUB + 1:
        raise ValueError(f"conv_silu kernels: d {d} must be a multiple of 128 "
                         f"and K {k} at most {_SUB + 1}")
    block_rows, block_d = _tiles(x.shape[1], d, block_rows, _SILU_BLOCK_D)
    block_d = math.gcd(at, block_d)     # a window starts on a block's edge
    return block_rows, block_d, _tiles(x.shape[1], block_d, block_rows,
                                       _CHANNELS)[1]


def _is_window(x, w, at: int, impl: str) -> bool:
    """Whether ``x`` is a wider array that holds the convolution's ``d``
    channels as its columns ``at : at + d``, which the kernels read as channel
    blocks of the array as it lies (what stands beside them takes a zero
    gradient). Static shapes alone."""
    if x.ndim != 3 or w.ndim != 2 or not (at or x.shape[2] > w.shape[0]):
        return False
    d = w.shape[0]
    if at < 0 or at + d > x.shape[2]:
        raise ValueError(f"conv_silu: columns {at}:{at + d} of x {x.shape}")
    if impl == "pallas" and at % 128:
        raise ValueError(f"conv_silu kernels: the window's first column {at} "
                         f"is not on a lane tile's edge (a multiple of 128)")
    return True


def _window_block(first: int):
    """Channel block ``c`` of the operand -> its block of a wider ``x`` whose
    window begins at block ``first`` (0: the index as it is, so that an
    operand as wide as the taps traces no ``+ 0``)."""
    return (lambda c: c + first) if first else (lambda c: c)


def _silu_forward_call(x, w, b, interpret: bool, at: int = 0):
    batch, length, _ = x.shape
    d, k = w.shape
    block_rows, block_d, channels = _silu_tiles(x, w, FWD_BLOCK_ROWS, at)
    block = pl.BlockSpec((1, block_rows, block_d), lambda c, s, i: (s, i, c))
    taps = lambda rows: pl.BlockSpec((rows, block_d), lambda c, s, i: (0, c))  # noqa: E731
    col = _window_block(at // block_d)
    x_block = pl.BlockSpec((1, block_rows, block_d),
                           lambda c, s, i: (s, i, col(c)))
    return named_pallas_call(
        "conv_silu_fwd",
        functools.partial(_silu_fwd_kernel, k=k, channels=channels),
        grid=(d // block_d, batch, pl.cdiv(length, block_rows)),
        in_specs=[x_block, taps(k), taps(1)],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((batch, length, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((_SUB, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(x, w.astype(jnp.float32).T, b.astype(jnp.float32)[None])


def _silu_backward_call(x, w, b, dy, interpret: bool, at: int = 0):
    batch, length, _ = x.shape
    d, k = w.shape
    block_rows, block_d, channels = _silu_tiles(x, w, BWD_BLOCK_ROWS, at)
    last_halo = pl.cdiv(length, _SUB) - 1
    per_block = block_rows // _SUB
    block = pl.BlockSpec((1, block_rows, block_d), lambda c, s, i: (s, i, c))
    after = pl.BlockSpec(       # the 16 rows after block i
        (1, _SUB, block_d),
        lambda c, s, i: (s, jnp.minimum((i + 1) * per_block, last_halo), c))
    taps = lambda rows: pl.BlockSpec((rows, block_d), lambda c, s, i: (0, c))  # noqa: E731
    col = _window_block(at // block_d)
    x_block = pl.BlockSpec((1, block_rows, block_d),
                           lambda c, s, i: (s, i, col(c)))
    x_after = pl.BlockSpec((1, _SUB, block_d), lambda c, s, i: (
        s, jnp.minimum((i + 1) * per_block, last_halo), col(c)))
    dy = dy.astype(x.dtype)
    dx, dw, db = named_pallas_call(
        "conv_silu_bwd",
        functools.partial(_silu_bwd_kernel, k=k, channels=channels,
                          length=length),
        grid=(d // block_d, batch, pl.cdiv(length, block_rows)),
        in_specs=[x_block, block, x_after, after, taps(k), taps(1)],
        out_specs=[block, taps(k), taps(1)],
        out_shape=[jax.ShapeDtypeStruct((batch, length, d), x.dtype),
                   jax.ShapeDtypeStruct((k, d), jnp.float32),
                   jax.ShapeDtypeStruct((1, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_SUB, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # dw and db are one block a channel block, summed over its grid
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(x, dy, x, dy, w.astype(jnp.float32).T, b.astype(jnp.float32)[None])
    return dx, dw.T.astype(w.dtype), db[0].astype(b.dtype)


# --------------------------------------------------------------- public op

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv(bcu, w, impl):
    if impl == "xla":
        return _plain(bcu, w)
    telemetry.counter("short_conv.calls").inc()
    return _forward_call(bcu, w, _flash._use_interpret())


def _conv_fwd(bcu, w, impl):
    return _conv(bcu, w, impl), (bcu, w)


def _conv_bwd(impl, residuals, dy):
    bcu, w = residuals
    if impl == "xla":
        return jax.vjp(_plain, bcu, w)[1](dy.astype(bcu.dtype))
    return _backward_call(bcu, w, dy, _flash._use_interpret())


_conv.defvjp(_conv_fwd, _conv_bwd)


def gated_short_conv(bcu: jax.Array, w: jax.Array, impl: str = "xla") -> jax.Array:
    """``y = C * causal_conv_w(B * u)`` for ``bcu = [B | C | u]`` (module
    docstring). bcu: ``[batch, L, 3d]``; w: ``[d, K]`` in any float dtype (its
    gradient comes back in it); ``impl``: ``"xla"`` (plain ``jax.numpy``) or
    ``"pallas"`` (``d`` a multiple of 128). Returns ``[batch, L, d]`` in
    ``bcu.dtype``. Differentiable in both; only ``bcu`` and ``w`` are kept for
    the backward.

    Under a mesh of several devices the kernels run per device
    (:func:`autodist_tpu.parallel.mesh.per_device`), the batch split over the
    data axes."""
    if impl not in IMPLS:
        raise ValueError(f"Unknown conv impl {impl!r}; valid: {IMPLS}")
    if impl == "xla":
        return _conv(bcu, w, impl)
    from autodist_tpu.parallel.mesh import per_device
    return per_device(functools.partial(_conv, impl=impl), (bcu, w),
                      batched=(True, False))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv_silu(x, w, b, impl):
    if impl == "xla":
        return _plain_silu(x, w, b)
    return _silu_forward_call(x, w, b, _flash._use_interpret())


def _conv_silu_fwd(x, w, b, impl):
    return _conv_silu(x, w, b, impl), (x, w, b)


def _conv_silu_bwd(impl, residuals, dy):
    x = residuals[0]
    if impl == "xla":
        return jax.vjp(_plain_silu, *residuals)[1](dy.astype(x.dtype))
    return _silu_backward_call(*residuals, dy, _flash._use_interpret())


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv_silu_window(x, w, b, at):
    """:func:`_conv_silu`'s kernels on columns ``at : at + d`` of a wider
    ``x``, read where they lie."""
    return _silu_forward_call(x, w, b, _flash._use_interpret(), at)


def _conv_silu_window_fwd(x, w, b, at):
    return _conv_silu_window(x, w, b, at), (x, w, b)


def _conv_silu_window_bwd(at, residuals, dy):
    x, w = residuals[:2]
    dx, dw, db = _silu_backward_call(*residuals, dy, _flash._use_interpret(), at)
    return (jnp.pad(dx, ((0, 0), (0, 0), (at, x.shape[2] - at - w.shape[0]))),
            dw, db)


_conv_silu_window.defvjp(_conv_silu_window_fwd, _conv_silu_window_bwd)


def conv_silu(x: jax.Array, w: jax.Array, b: jax.Array,
              impl: str = "xla", at: int = 0) -> jax.Array:
    """``y_t = silu(sum_j w[:, j] * x_{t-(K-1)+j} + b)``, depthwise over the
    channels, causal, each sequence on its own (``x_s = 0`` for ``s < 0``).
    x: ``[batch, L, d]``, or a wider array whose columns ``at : at + d`` are
    the operand (a projection's output handed whole: the kernels read the
    window where it lies, ``at`` a multiple of 128, and nothing is cut out
    first); w: ``[d, K]``; b: ``[d]``; ``impl``: ``"xla"`` or ``"pallas"``
    (``d`` a multiple of 128). Returns ``[batch, L, d]`` in ``x.dtype``; the
    arithmetic is float32. Differentiable in all three; only they are kept
    for the backward. Under a mesh of several devices the kernels run per
    device, as :func:`gated_short_conv`'s do. The gauge
    ``short_conv.operands_relaid`` counts the wide operands a kernel call was
    handed cut apart (1, or 0 for a window)."""
    if impl not in IMPLS:
        raise ValueError(f"Unknown conv impl {impl!r}; valid: {IMPLS}")
    wide = _is_window(x, w, at, impl)
    if impl == "xla":
        if wide:
            x = x[..., at:at + w.shape[0]]
        return _conv_silu(x, w, b, impl)
    from autodist_tpu.parallel.mesh import per_device
    telemetry.gauge("short_conv.operands_relaid").set(0 if wide else 1)
    run = (functools.partial(_conv_silu_window, at=at) if wide
           else functools.partial(_conv_silu, impl=impl))
    return per_device(run, (x, w, b), batched=(True, False, False))
