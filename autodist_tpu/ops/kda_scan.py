"""Kimi Delta Attention's recurrence — a gated delta rule with a decay a
channel (Kimi Team, "Kimi Linear", arXiv:2510.26692) — as a chunked operator,
plain and as two pallas TPU kernels, forward and backward.

``kda_scan(q, k, v, g [B, L, H, D], beta [B, L, H]) -> o [B, L, H, D]``: per
sequence and head, with ``alpha_t = exp(g_t)`` (``g`` in ``[-5, 0]``, float32:
a decay a channel of the key) and ``S`` in ``R^{D x D}``, ``S_0 = 0``::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

the state decays a channel, is corrected by its own prediction error along
``k_t`` (the delta rule) and read by ``q_t``. No state a token is ever built.
A sequence is cut into chunks of ``C`` positions; with ``G`` the running sum
of ``g`` inside a chunk (``G_C`` its last row), ``S`` the state that enters
the chunk and ``*`` a product a row and channel::

    A  = strict_lower((K * e^G)(K * e^-G)^T) * beta         a row of A times its beta
    M  = (I + A)^-1                                         the WY form's triangular solve
    U  = M (beta * (V - (K * e^G) S))                       the chunk's corrected values
    O  = (Q * e^G) S + lower_with_diagonal((Q * e^G)(K * e^-G)^T) U
    S' = Diag(e^{G_C}) S + (K * e^{G_C - G})^T U

**Float32's range.** ``e^-G`` alone overflows (64 rows x 5 = 320), so the two
planes are made a sub-block of 16 target rows at a time: with ``N_a`` the
running sum before sub-block ``a``, its rows carry ``e^{G - N_a}`` (at most
1) and every source row ``e^{min(N_a - G, 80)}``: at most 1 for the rows
before the sub-block, at most ``e^80`` inside it (16 rows x 5, which is what
the model's bound of -5 a step is for) and anything finite for the rows after
it, which the triangle's mask drops. The backward takes the same planes'
transposes the same way. ``g`` below -5 a step is outside the operator.

**The solve** is the doubling ``(I + A)^-1 = (I - A)(I + A^2)(I + A^4) ...``
of a nilpotent ``A``: ten ``[C, C]`` products in float32 at full precision,
every one a whole matrix product (a forward substitution over 16-row
sub-blocks costs as many, smaller and in a longer chain). The running sum is a
product with a triangle of ones, float32 at full precision too; every other
product takes its operands in ``q.dtype`` (bfloat16 in the models) and
accumulates in float32; ``g``, ``G``, every ``exp`` and the state are float32.

The custom VJP keeps its inputs and the state that enters each chunk (``[B,
L/C, H, D, D]`` float32: 128 MB a layer at 8,192 tokens and 16 heads of 128),
never an ``[L, L]`` plane, and makes ``A``, ``M`` and ``U`` again. The backward
is the chunked scan in reverse: with ``dO`` and the cotangent ``dS'`` of the
state a chunk leaves (``R = beta * (V - (K * e^G) S)``, ``U = M R``)::

    dU = lower(..)^T dO + (K * e^{G_C - G}) dS'        dR = M^T dU
    d lower(..) = lower(dO U^T)        dA = -strict_lower(dR U^T)
    dS = (Q * e^G)^T dO + Diag(e^{G_C}) dS' - (K * e^G)^T (beta * dR)

and the gradients of ``q``, ``k``, ``v``, ``beta`` follow from the chunk
alone; ``dG`` is ``q * dq`` plus ``k`` times the part of ``dk`` that came
through ``e^G`` less the parts through ``e^-G`` and ``e^{G_C - G}``, and ``dg``
its running sum from the chunk's end.

One body of arithmetic (:func:`_chunk_forward`, :func:`_chunk_backward`: one
head, one chunk, two-dimensional values) serves both forms:

- ``impl="xla"``: that body under ``vmap`` over sequences and heads and
  ``lax.scan`` over the chunks. Init, the CPU and the comparison run it.
- ``impl="pallas"``: ``kda_fwd`` and ``kda_bwd``, grid (sequence, head, chunk)
  with the chunks in order (reversed for the backward) and the head's state,
  transposed (``[D_v, D_k]``: the decay a channel then scales its lanes), in
  VMEM between them. ``q``, ``k``, ``v``, ``g`` and the result are read and
  written as ``[B, L, H D]`` rows, a head's 128 columns where they lie (what
  ``conv_silu`` writes and the output norm reads); ``beta`` crosses as ``[B, H,
  L/C, 1, C]`` rows and is turned to a column against an identity mask.

On the CPU backend the kernels run in pallas interpret mode;
``tests/test_chip_compile.py`` compiles them for a described v5e at the Ling
cell's shape.
"""

import functools
import importlib
import types

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
_SUB = 16               # target rows a plane is made for at a time
_CAP = 80.0             # the largest exponent inside a sub-block: 16 rows x 5
# ``jax.ad_checkpoint.checkpoint_name`` of what the forward rule makes: the
# output and the chunks' states
KEPT_NAME = "kda_residuals"


# ------------------------------------------------- one head, one chunk

def _dot(a, b, contract, dtype=None):
    """``a`` and ``b`` contracted over ``contract = (axis of a, axis of b)``,
    float32 out. ``dtype`` None: float32 operands (the running sums and the
    solve). Float32 operands are multiplied at full precision: the chip's
    default rounds them to bfloat16 for one pass, here and in XLA."""
    dims = (((contract[0],), (contract[1],)), ((), ()))
    if dtype is None or jnp.dtype(dtype) == jnp.float32:
        return jax.lax.dot_general(
            a.astype(jnp.float32), b.astype(jnp.float32), dims,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                               preferred_element_type=jnp.float32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _blocks(c: int):
    return [slice(a * _SUB, (a + 1) * _SUB) for a in range(c // _SUB)]


def _stack(*parts):
    return jnp.concatenate(parts, axis=0)


def _inverse(a):
    """``(I + a)^-1`` of a strictly lower ``[C, C]``: the doubling."""
    c = a.shape[0]
    inv = (_iota((c, c), 0) == _iota((c, c), 1)).astype(jnp.float32) - a
    power, span = a, 2
    while span < c:         # inv holds the powers below ``span``
        power = _dot(power, power, (1, 0))
        inv = inv + _dot(inv, power, (1, 0))
        span *= 2
    return inv


def _prepare(q, k, g, beta_row, dtype):
    """What a chunk's forward and backward share and the state has no part
    in: the running sums, the two triangles, the solve."""
    c, d = q.shape
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    rows = _iota((c, d), 0)
    qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
    run = _dot((row >= col).astype(jnp.float32), g, (1, 0))          # G

    def at(r):      # G's row r as [1, D] (row -1: zeros)
        return jnp.sum(jnp.where(rows == r, run, 0.0), axis=0, keepdims=True)

    blocks = _blocks(c)
    starts = [at(a * _SUB - 1) for a in range(len(blocks))]          # N_a
    last = at(c - 1)                                                 # G_C
    start_of_row = starts[0]
    for a in range(1, len(blocks)):
        start_of_row = jnp.where(rows >= a * _SUB, starts[a], start_of_row)
    inside = jnp.exp(run - start_of_row)                 # e^{G - N_a}, <= 1
    q_in, k_in = qf * inside, kf * inside
    # e^{min(N_a - G, cap)} of every source row, a target sub-block
    ends = [jnp.exp(jnp.minimum(start - run, _CAP)) for start in starts]
    k_ends = [kf * end for end in ends]
    targets = [_stack(q_in[blk], k_in[blk]) for blk in blocks]       # [32, D]
    planes = [_dot(target, k_end, (1, 1), dtype)
              for target, k_end in zip(targets, k_ends)]             # [32, C]
    a_qk = jnp.where(row >= col, _stack(*(p[:_SUB] for p in planes)), 0.0)
    a_kk = jnp.where(row > col, _stack(*(p[_SUB:] for p in planes)), 0.0)
    eye = row == col
    beta_col = jnp.sum(jnp.where(eye, beta_row.astype(jnp.float32), 0.0),
                       axis=1, keepdims=True)                        # [C, 1]
    here, to_end = jnp.exp(run), jnp.exp(last - run)
    return types.SimpleNamespace(
        row=row, col=col, rows=rows, eye=eye, blocks=blocks, qf=qf, kf=kf,
        inside=inside, targets=targets, ends=ends, k_ends=k_ends, a_qk=a_qk,
        a_kk=a_kk, beta_col=beta_col, solve=_inverse(beta_col * a_kk),
        here=here, q_here=qf * here, k_here=kf * here, to_end=to_end,
        k_to_end=kf * to_end, left=jnp.exp(last))                    # e^{G_C}


def _corrected(p, v, state, dtype):
    """``(V - (K * e^G) S, U)``; the state is ``[D_v, D_k]``."""
    error = v.astype(jnp.float32) - _dot(p.k_here, state, (1, 1), dtype)
    return error, _dot(p.solve, p.beta_col * error, (1, 0), dtype)


def _chunk_forward(q, k, v, g, beta_row, state, dtype):
    """``(o [C, D_v], the state the chunk leaves [D_v, D_k])``, float32.
    q, k, g: ``[C, D_k]``; v: ``[C, D_v]``; beta_row: ``[1, C]``."""
    p = _prepare(q, k, g, beta_row, dtype)
    _, u = _corrected(p, v, state, dtype)
    o = _dot(p.q_here, state, (1, 1), dtype) + _dot(p.a_qk, u, (1, 0), dtype)
    return o, p.left * state + _dot(u, p.k_to_end, (0, 0), dtype)


def _chunk_backward(q, k, v, g, beta_row, state, do, d_left, dtype):
    """The chunk's gradients from ``do [C, D_v]`` and the cotangent ``d_left
    [D_v, D_k]`` of the state it leaves: ``(dq, dk, dv, dg, dbeta_row, the
    cotangent of the state it was handed)``, float32."""
    p = _prepare(q, k, g, beta_row, dtype)
    c = q.shape[0]
    error, u = _corrected(p, v, state, dtype)
    dof = do.astype(jnp.float32)
    d_u = (_dot(p.a_qk, dof, (0, 0), dtype)
           + _dot(p.k_to_end, d_left, (1, 1), dtype))
    d_r = _dot(p.solve, d_u, (0, 0), dtype)
    planes = _dot(_stack(dof, d_r), u, (1, 1), dtype)                # [2C, C]
    d_qk = jnp.where(p.row >= p.col, planes[:c], 0.0)
    d_a = -jnp.where(p.row > p.col, planes[c:], 0.0)
    d_kk = p.beta_col * d_a
    d_beta = (jnp.sum(d_r * error, axis=1, keepdims=True)
              + jnp.sum(d_a * p.a_kk, axis=1, keepdims=True))        # [C, 1]
    dv = p.beta_col * d_r
    through = _dot(_stack(dof, dv), state, (1, 0), dtype)            # [2C, D_k]
    dq_parts, dk_parts = [], []
    dk_back = jnp.zeros_like(p.kf)          # through e^-G
    for blk, target, end, k_end in zip(p.blocks, p.targets, p.ends, p.k_ends):
        x = _stack(d_qk[blk], d_kk[blk])                             # [32, C]
        forth = _dot(x, k_end, (1, 0), dtype)                        # [32, D_k]
        dq_parts.append(p.inside[blk] * forth[:_SUB])
        dk_parts.append(p.inside[blk] * forth[_SUB:])
        dk_back += _dot(x, target, (0, 0), dtype) * end
    dq = through[:c] * p.here + _stack(*dq_parts)
    dk_here = _stack(*dk_parts) - through[c:] * p.here               # through e^G
    dk_end = _dot(u, d_left, (1, 0), dtype) * p.to_end               # through e^{G_C - G}
    dk = dk_here + dk_back + dk_end
    d_run = p.qf * dq + p.kf * (dk_here - dk_back - dk_end)
    at_end = (jnp.sum(p.kf * dk_end, axis=0, keepdims=True)
              + p.left * jnp.sum(state * d_left, axis=0, keepdims=True))
    d_run = d_run + jnp.where(p.rows == c - 1, at_end, 0.0)
    dg = _dot((p.row <= p.col).astype(jnp.float32), d_run, (1, 0))
    d_state = (_dot(_stack(dof, -dv), _stack(p.q_here, p.k_here), (0, 0), dtype)
               + p.left * d_left)
    d_beta_row = jnp.sum(jnp.where(p.eye, d_beta, 0.0), axis=0, keepdims=True)
    return dq, dk, dv, dg, d_beta_row, d_state


# ------------------------------------------------------------- the xla form

def _chunked(t, heads: int, chunk: int):
    """``[B, L, H D] -> [L/C, B, H, C, D]``."""
    b, length, wide = t.shape
    t = t.reshape(b, length // chunk, chunk, heads, wide // heads)
    return jnp.transpose(t, (1, 0, 3, 2, 4))


def _rows(t):
    """``[L/C, B, H, C, D] -> [B, L, H D]``."""
    nc, b, h, c, d = t.shape
    return jnp.transpose(t, (1, 0, 3, 2, 4)).reshape(b, nc * c, h * d)


def _beta_rows(beta, chunk: int):
    """``[B, L, H] -> [B, H, L/C, 1, C]`` float32."""
    b, length, h = beta.shape
    return jnp.transpose(beta.astype(jnp.float32), (0, 2, 1)).reshape(
        b, h, length // chunk, 1, chunk)


def _beta_back(d_rows, dtype):
    """``[B, H, L/C, 1, C] -> [B, L, H]``."""
    b, h, nc, _, c = d_rows.shape
    return jnp.transpose(d_rows.reshape(b, h, nc * c), (0, 2, 1)).astype(dtype)


def _over_heads(fn, dtype):
    return jax.vmap(jax.vmap(functools.partial(fn, dtype=dtype)))


def _xla_forward(q, k, v, g, beta, chunk: int):
    """``(o [B, L, H D], states [B, L/C, H, D_v, D_k], the last state)``."""
    b, _, h = beta.shape
    d = q.shape[2] // h
    step = _over_heads(_chunk_forward, q.dtype)

    def walk(state, chunk_of):
        o, left = step(*chunk_of, state)
        return left, (o, state)

    operands = (*(_chunked(t, h, chunk) for t in (q, k, v, g)),
                jnp.moveaxis(_beta_rows(beta, chunk), 2, 0))
    last, (o, states) = jax.lax.scan(
        walk, jnp.zeros((b, h, d, d), jnp.float32), operands)
    return _rows(o).astype(q.dtype), jnp.moveaxis(states, 0, 1), last


def _xla_backward(q, k, v, g, beta, states, do, chunk: int):
    h = beta.shape[2]
    step = _over_heads(_chunk_backward, q.dtype)

    def walk(d_left, chunk_of):
        *grads, d_state = step(*chunk_of, d_left)
        return d_state, grads

    operands = (*(_chunked(t, h, chunk) for t in (q, k, v, g)),
                jnp.moveaxis(_beta_rows(beta, chunk), 2, 0),
                jnp.moveaxis(states, 1, 0), _chunked(do, h, chunk))
    _, (dq, dk, dv, dg, d_beta) = jax.lax.scan(
        walk, jnp.zeros_like(states[:, 0]), operands, reverse=True)
    return (_rows(dq).astype(q.dtype), _rows(dk).astype(k.dtype),
            _rows(dv).astype(v.dtype), _rows(dg).astype(g.dtype),
            _beta_back(jnp.moveaxis(d_beta, 0, 2), beta.dtype))


# ----------------------------------------------------------------- kernels

def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, states_ref,
                last_ref, state_ref):
    @pl.when(pl.program_id(2) == 0)
    def _first_chunk_of_a_sequence():
        state_ref[...] = jnp.zeros_like(state_ref)

    state = state_ref[...]
    states_ref[0, 0, 0] = state
    o, left = _chunk_forward(q_ref[0], k_ref[0], v_ref[0], g_ref[0],
                             beta_ref[0, 0, 0], state, q_ref.dtype)
    o_ref[0] = o.astype(o_ref.dtype)
    state_ref[...] = left
    last_ref[0, 0] = left


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate_ref):
    @pl.when(pl.program_id(2) == 0)
    def _last_chunk_of_a_sequence():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    dq, dk, dv, dg, d_beta, d_state = _chunk_backward(
        q_ref[0], k_ref[0], v_ref[0], g_ref[0], beta_ref[0, 0, 0],
        states_ref[0, 0, 0], do_ref[0], dstate_ref[...], q_ref.dtype)
    dq_ref[0] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    dg_ref[0] = dg.astype(dg_ref.dtype)
    dbeta_ref[0, 0, 0] = d_beta
    dstate_ref[...] = d_state


def _specs(chunk: int, d: int, order):
    """Block specs by name, for a grid (sequence, head, chunk) whose chunk
    index maps through ``order`` (the backward walks down)."""
    return dict(
        rows=pl.BlockSpec((1, chunk, d), lambda b, h, c: (b, order(c), h)),
        beta=pl.BlockSpec((1, 1, 1, 1, chunk),
                          lambda b, h, c: (b, h, order(c), 0, 0)),
        states=pl.BlockSpec((1, 1, 1, d, d),
                            lambda b, h, c: (b, order(c), h, 0, 0)),
        last=pl.BlockSpec((1, 1, d, d), lambda b, h, c: (b, h, 0, 0)))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _forward_call(q, k, v, g, beta, chunk: int, interpret: bool):
    b, length, h = beta.shape
    d, nc = q.shape[2] // h, length // chunk
    spec = _specs(chunk, d, lambda c: c)
    return named_pallas_call(
        "kda_fwd", _fwd_kernel, grid=(b, h, nc),
        in_specs=[spec["rows"]] * 4 + [spec["beta"]],
        out_specs=[spec["rows"], spec["states"], spec["last"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, nc, h, d, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, d, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
    )(q, k, v, g, _beta_rows(beta, chunk))


def _backward_call(q, k, v, g, beta, states, do, chunk: int, interpret: bool):
    b, length, h = beta.shape
    d, nc = q.shape[2] // h, length // chunk
    spec = _specs(chunk, d, lambda c: nc - 1 - c)
    like = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype)  # noqa: E731
    dq, dk, dv, dg, d_beta = named_pallas_call(
        "kda_bwd", _bwd_kernel, grid=(b, h, nc),
        in_specs=[spec["rows"]] * 4 + [spec["beta"], spec["states"],
                                       spec["rows"]],
        out_specs=[spec["rows"]] * 4 + [spec["beta"]],
        out_shape=[like(q), like(k), like(v), like(g),
                   jax.ShapeDtypeStruct((b, h, nc, 1, chunk), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=_params(), interpret=interpret,
    )(q, k, v, g, _beta_rows(beta, chunk), states, do.astype(q.dtype))
    return dq, dk, dv, dg, _beta_back(d_beta, beta.dtype)


# --------------------------------------------------------------- public op

def _forward(q, k, v, g, beta, chunk, impl):
    if impl == "xla":
        return _xla_forward(q, k, v, g, beta, chunk)
    return _forward_call(q, k, v, g, beta, chunk, _flash._use_interpret())


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(q, k, v, g, beta, chunk, impl):
    return _forward(q, k, v, g, beta, chunk, impl)[0]


def _scan_fwd(q, k, v, g, beta, chunk, impl):
    o, states, _ = _forward(q, k, v, g, beta, chunk, impl)
    # what a caller's ``jax.checkpoint`` may keep by name, so that its
    # backward does not run the forward again (``_flash_fwd``'s comment)
    o, states = checkpoint_name((o, states), KEPT_NAME)
    return o, (q, k, v, g, beta, states)


def _scan_bwd(chunk, impl, residuals, do):
    if impl == "xla":
        return _xla_backward(*residuals, do, chunk)
    return _backward_call(*residuals, do, chunk, _flash._use_interpret())


_scan.defvjp(_scan_fwd, _scan_bwd)


def state_kept_bytes(batch: int, length: int, heads: int, d: int,
                     chunk: int) -> int:
    """Bytes of the chunks' states one call keeps for its backward."""
    return batch * -(-length // chunk) * heads * d * d * 4


def _checked(q, k, v, g, beta, chunk: int, impl: str):
    """The operands as ``[B, L, H D]`` rows (``beta [B, L, H]`` as it is),
    padded to whole chunks, or a refusal that names what is wrong."""
    if impl not in IMPLS:
        raise ValueError(f"Unknown kda impl {impl!r}; valid: {IMPLS}")
    if beta.ndim != 3 or beta.shape[:2] != q.shape[:2]:
        raise ValueError(f"kda_scan: beta {beta.shape} beside q {q.shape}; "
                         f"want [B, L, H]")
    b, length, h = beta.shape
    wide = q.shape[-1] * (h if q.ndim == 4 else 1)
    if wide % h or any(t.shape not in ((b, length, wide),
                                       (b, length, h, wide // h))
                       for t in (q, k, v, g)):
        raise ValueError(
            f"kda_scan: q {q.shape}, k {k.shape}, v {v.shape}, g {g.shape}; "
            f"want [B, L, H, D] (or [B, L, H D] rows) four times, H = {h}")
    rows = [t.reshape(b, length, wide) for t in (q, k, v, g)]
    d = wide // h
    if chunk % _SUB:
        raise ValueError(f"kda_scan: chunk {chunk} is not whole sub-blocks "
                         f"of {_SUB}")
    if impl == "pallas" and (d % 128 or chunk % 64):
        raise ValueError(f"kda_scan kernels: head width {d} must be a "
                         f"multiple of 128 and chunk {chunk} of 64")
    pad = -length % chunk
    if pad:     # g = 0, beta = 0: nothing decays, nothing is written
        rows = [jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in rows]
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    q, k, v, g = rows
    return q, k, v.astype(q.dtype), g.astype(jnp.float32), beta, d


def kda_scan(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array, *, chunk: int = 64,
             impl: str = "xla") -> jax.Array:
    """``o_t = S_t^T q_t`` with ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t))
    S_{t-1} + beta_t k_t v_t^T`` (module docstring). q, k, v, g: ``[batch, L,
    H, D]``, or ``[batch, L, H D]`` rows (what the kernels address: nothing
    is laid out anew around them); ``g`` the log-decay a channel, in ``[-5,
    0]``, taken as float32; beta: ``[batch, L, H]``; ``impl``: ``"xla"`` or
    ``"pallas"`` (``D`` a multiple of 128, ``chunk`` of 64). Returns the shape
    of ``q`` in ``q.dtype``. Differentiable in all five; the inputs and one
    ``[D, D]`` float32 state a chunk and head are kept for the backward
    (gauge ``kda.state_kept_bytes``).

    Under a mesh of several devices the kernels run per device
    (:func:`autodist_tpu.parallel.mesh.per_device`), the batch split over the
    data axes."""
    shape, length = q.shape, q.shape[1]
    q, k, v, g, beta, d = _checked(q, k, v, g, beta, chunk, impl)
    b, padded, h = beta.shape
    telemetry.counter("kda.calls").inc()
    telemetry.gauge("kda.chunk").set(chunk)
    telemetry.gauge("kda.chunks").set(b * padded // chunk)
    telemetry.gauge("kda.heads").set(h)
    telemetry.gauge("kda.state_kept_bytes").set(
        state_kept_bytes(b, padded, h, d, chunk))
    run = functools.partial(_scan, chunk=chunk, impl=impl)
    if impl == "pallas":
        from autodist_tpu.parallel.mesh import per_device
        o = per_device(run, (q, k, v, g, beta), batched=(True,) * 5)
    else:
        o = run(q, k, v, g, beta)
    return o[:, :length].reshape(shape)


def kda_last_state(q, k, v, g, beta, *, chunk: int = 64,
                   impl: str = "xla") -> jax.Array:
    """``S_L [batch, H, D_k, D_v]`` float32, the state after the last
    position: what a decoder would carry on from. Not differentiable."""
    q, k, v, g, beta, _ = _checked(q, k, v, g, beta, chunk, impl)
    last = _forward(q, k, v, g, beta, chunk, impl)[2]
    return jnp.swapaxes(jax.lax.stop_gradient(last), 2, 3)
