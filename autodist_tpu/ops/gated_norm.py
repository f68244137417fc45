"""Gated grouped RMSNorm — what stands between the Mamba-2 scan and the
output projection (Nemotron-H), plain and as two pallas TPU kernels.

``gated_norm(y [B, L, d], z [B, L, >= d], scale [d], groups, eps, dtype) ->
[B, L, d]`` in ``dtype``: with ``z`` the first ``d`` columns of the array
handed (the input projection's ``[z | xBC | dt]`` whole, or ``z`` alone)::

    u   = y * silu(z)                                    float32
    out = u * rsqrt(mean(u^2 over the run) + eps) * scale

a run being one of ``groups`` equal stretches of the ``d`` columns
(:func:`gated_group_norm`: the equations as they stand).

No matrix product and nothing kept but the operands: the operator moves
``y``, ``z`` in and the result out forward, and ``y``, ``z``, the result's
cotangent in and ``dy``, ``dz`` out backward. As ``jax.numpy`` on the chip it
moved ten times that: a reduce over ``[.., groups, d / groups]`` makes XLA lay
the float32 rows out anew as ``[rows / 8, groups, 8, d / groups]`` and back,
forward, under a checkpoint and in the transpose (PERF.md section 6, "PR
49"). A run of 512 columns is four whole lane tiles of a row, so its mean
square is a sum along lanes of a ``[rows, 512]`` block:

- ``gated_norm_fwd``, ``gated_norm_bwd``: grid (run, sequence, row block). A
  grid step reads a run's columns of a block of rows of ``y`` and of ``z``
  where they lie (``z`` as a column block of the wider array), walks them 16
  rows at a time in float32 and writes the result's, or ``dy``'s and
  ``dz``'s, columns; the scale's gradient is summed over the rows of the call
  in an output block that stays in VMEM while a run's row blocks pass.

With ``n = u r``, ``r`` the ``rsqrt`` and ``gs = g * scale`` for the result's
cotangent ``g``::

    du = r (gs - n mean(gs n))      dy = du silu(z)      dz = du y silu'(z)
    dscale = sum over rows of g n

``impl="xla"`` is :func:`gated_group_norm` on the cut-out ``z`` under plain
autodiff (init, the CPU and the comparison run it). On the CPU backend the
kernels run in pallas interpret mode; ``tests/test_chip_compile.py`` compiles
them for a described v5e at the Nemotron cell's shape.
"""

import functools
import importlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.ops.named_call import named_pallas_call

# The module, as ``grouped_matmul`` reads it: a compile rehearsal that steers
# the flash kernels to compile steers these too.
_flash = importlib.import_module("autodist_tpu.ops.flash_attention")

IMPLS = ("xla", "pallas")
_SUB = 16               # rows a walk step takes: one packed bfloat16 tile
# Rows a grid step holds of one run. At 512 columns a block is 1 MiB in
# bfloat16; the backward's five, double-buffered, 10 (20 for the exact first
# layer's float32 z, result and dz).
BLOCK_ROWS = 1024
_VMEM_LIMIT = 48 << 20


def gated_group_norm(y, z, scale, groups: int, eps: float):
    """``RMSNorm_grouped(y * silu(z)) * scale``: the mean square over each of
    ``groups`` equal runs of the last axis, float32."""
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    runs = gated.reshape(*gated.shape[:-1], groups, -1)
    runs = runs * jax.lax.rsqrt(jnp.mean(jnp.square(runs), axis=-1,
                                         keepdims=True) + eps)
    return runs.reshape(gated.shape) * scale


# ----------------------------------------------------------------- kernels

def _strip(r):
    """The 16 rows of walk step ``r``, and a reader of them in float32."""
    rows = pl.ds(pl.multiple_of(r * _SUB, _SUB), _SUB)
    return rows, lambda ref: ref[0, rows, :].astype(jnp.float32)


def _fwd_kernel(y_ref, z_ref, scale_ref, o_ref, *, eps: float):
    scale = scale_ref[...]

    def walk(r, carry):
        rows, f32 = _strip(r)
        z = f32(z_ref)
        u = f32(y_ref) * (z * jax.nn.sigmoid(z))
        n = u * jax.lax.rsqrt(jnp.mean(u * u, axis=1, keepdims=True) + eps)
        o_ref[0, rows, :] = (n * scale).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, o_ref.shape[1] // _SUB, walk, 0)


def _bwd_kernel(y_ref, z_ref, g_ref, scale_ref, dy_ref, dz_ref, dscale_ref, *,
                eps: float, length: int):
    _, block_rows, width = g_ref.shape
    i = pl.program_id(2)

    @pl.when((pl.program_id(1) == 0) & (i == 0))
    def _first_block_of_the_run():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    scale = scale_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (_SUB, width), 0)

    def walk(r, sums):
        rows, f32 = _strip(r)
        y, z, g = f32(y_ref), f32(z_ref), f32(g_ref)
        sig = jax.nn.sigmoid(z)
        silu = z * sig
        u = y * silu
        rs = jax.lax.rsqrt(jnp.mean(u * u, axis=1, keepdims=True) + eps)
        n, gs = u * rs, g * scale
        du = rs * (gs - n * jnp.mean(gs * n, axis=1, keepdims=True))
        dy_ref[0, rows, :] = (du * silu).astype(dy_ref.dtype)
        dz_ref[0, rows, :] = (du * y * (sig * (1.0 + z * (1.0 - sig)))
                              ).astype(dz_ref.dtype)
        # the last block's rows past the sequence hold anything
        inside = i * block_rows + r * _SUB + row < length
        return sums + jnp.where(inside, g * n, 0.0)

    sums = jax.lax.fori_loop(0, block_rows // _SUB, walk,
                             jnp.zeros((_SUB, width), jnp.float32))
    dscale_ref[...] += jnp.sum(sums, axis=0, keepdims=True)


# ------------------------------------------------------------------- calls

def _blocks(y, groups: int):
    """``(grid, a run's block of rows, a run's block of the scale)``."""
    batch, length, d = y.shape
    width = d // groups
    block_rows = min(BLOCK_ROWS, -(-length // _SUB) * _SUB)
    return ((groups, batch, pl.cdiv(length, block_rows)),
            pl.BlockSpec((1, block_rows, width), lambda j, s, i: (s, i, j)),
            pl.BlockSpec((1, width), lambda j, s, i: (0, j)))


def _forward_call(y, z, scale, groups, eps, dtype, interpret: bool):
    grid, block, run = _blocks(y, groups)
    return named_pallas_call(
        "gated_norm_fwd", functools.partial(_fwd_kernel, eps=eps),
        grid=grid, in_specs=[block, block, run], out_specs=block,
        out_shape=jax.ShapeDtypeStruct(y.shape, dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(y, z, scale.astype(jnp.float32)[None])


def _backward_call(y, z, scale, g, groups, eps, interpret: bool):
    grid, block, run = _blocks(y, groups)
    dy, dz, dscale = named_pallas_call(
        "gated_norm_bwd",
        functools.partial(_bwd_kernel, eps=eps, length=y.shape[1]),
        grid=grid, in_specs=[block, block, block, run],
        out_specs=[block, block, run],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(y.shape, z.dtype),
                   jax.ShapeDtypeStruct((1, y.shape[2]), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # dscale is one block a run, summed over its grid
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(y, z, g, scale.astype(jnp.float32)[None])
    # z's cotangent is the wide array's: zeros behind its d columns
    behind = z.shape[2] - y.shape[2]
    if behind:
        dz = jnp.pad(dz, ((0, 0), (0, 0), (0, behind)))
    return dy, dz, dscale[0].astype(scale.dtype)


# --------------------------------------------------------------- public op

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _norm(y, z, scale, groups, eps, dtype):
    return _forward_call(y, z, scale, groups, eps, dtype,
                         _flash._use_interpret())


def _norm_fwd(y, z, scale, groups, eps, dtype):
    return _norm(y, z, scale, groups, eps, dtype), (y, z, scale)


def _norm_bwd(groups, eps, dtype, residuals, g):
    return _backward_call(*residuals, g, groups, eps,
                          _flash._use_interpret())


_norm.defvjp(_norm_fwd, _norm_bwd)


def gated_norm(y: jax.Array, z: jax.Array, scale: jax.Array, groups: int,
               eps: float, dtype=None, impl: str = "xla") -> jax.Array:
    """``RMSNorm_grouped(y * silu(z)) * scale`` (module docstring). y: ``[batch,
    L, d]``; z: ``[batch, L, d]`` or wider, its first ``d`` columns read where
    they lie (what stands behind them takes a zero gradient); scale: ``[d]``;
    ``dtype``: the result's (``y``'s if None); ``impl``: ``"xla"`` or
    ``"pallas"`` (a run, ``d / groups`` columns, a multiple of 128). The
    arithmetic is float32. Differentiable in all three; only they are kept for
    the backward. Under a mesh of several devices the kernels run per device
    (:func:`autodist_tpu.parallel.mesh.per_device`)."""
    if impl not in IMPLS:
        raise ValueError(f"Unknown gated norm impl {impl!r}; valid: {IMPLS}")
    d = y.shape[-1]
    if (y.ndim != 3 or z.ndim != 3 or z.shape[:2] != y.shape[:2]
            or z.shape[2] < d or scale.shape != (d,) or d % groups):
        raise ValueError(
            f"gated_norm: y {y.shape}, z {z.shape}, scale {scale.shape}, "
            f"{groups} groups; want [B, L, d], [B, L, d or wider], [d] with "
            f"the groups dividing d")
    dtype = jnp.dtype(dtype or y.dtype)
    if impl == "xla":
        return gated_group_norm(y, z[..., :d], scale, groups, eps).astype(dtype)
    if (d // groups) % 128:
        raise ValueError(f"gated_norm kernels: a run of {d // groups} columns "
                         f"is not a multiple of 128")
    from autodist_tpu.parallel.mesh import per_device
    return per_device(
        functools.partial(_norm, groups=groups, eps=eps, dtype=dtype),
        (y, z, scale), batched=(True, True, False))
