"""Plain float32 reference of Jamba's training loss, written from the
published ``config.json`` of ``ai21labs/AI21-Jamba2-3B`` and HF's
``modeling_jamba.py`` semantics, not from the system's code: no kernel, no
chunk states, no bfloat16, no flax, no sharding of its own. The parameter tree
is read by name.

``x0 = E[tokens]``. Layer ``i`` is ``h = x + mixer(RMSNorm_in(x))``, ``x = h +
W_down(silu(W_gate u) * W_up u)`` with ``u = RMSNorm_ff(h)``; the mixer is
attention iff ``i % attn_period == attn_offset``, else Mamba-1:

``[x | z] = u W_in``; ``x_t = silu(sum_j w[:, j] x_{t-(K-1)+j} + b)``
(depthwise, causal, zeros before the sequence); ``[r | B | C] = x W_x``, each
under its own RMSNorm (Jamba's three inner norms); ``dt = softplus(r W_dt +
b_dt)``; ``A = -exp(A_log)``. The recurrence is computed **literally**, one
token after another::

    s_t = exp(dt_t (x) A) . s_{t-1} + (dt_t . x_t) (x) B_t          s [E, N], s_0 = 0
    y_t = s_t C_t + D . x_t

as a ``lax.scan`` over the tokens. Its backward through 16,384 steps must not
keep a ``[L, E, N]`` history (5.4 GB a layer), so the loop is nested: chunks
of ``TOKEN_BLOCK`` tokens, each under ``jax.checkpoint`` (one ``[E, N]`` state
a chunk is kept and a chunk's own states are made again for its backward:
that changes no number). Then ``(y . silu(z)) W_out``.

Attention: q, k, v without bias, norm or positional encoding of any kind;
softmax attention at ``1 / sqrt(head_dim)`` over the keys ``j <= i``, query
head ``n`` reading KV head ``n // group`` (``reference/afmoe.py``
``banded_attention`` without a window, a block of queries at a time); the
output projection.

After the last layer RMSNorm and the tied table as the head; mean next-token
cross-entropy, ``ROW_BLOCK`` rows of logits at a time under
``jax.checkpoint`` (16,384 x 65,536 float32 logits are 4.3 GB). Each layer
sits under ``jax.checkpoint`` too, its mixer and its MLP each under one of
their own and the MLP ``ROW_BLOCK`` positions at a time, so that one sequence
of 16,384 in float32 fits a chip beside its share of the parameters and two
gradient trees: blocking changes no number. A run of consecutive layers of
one kind is one ``lax.scan`` over their stacked parameters (the same layers
in the same order: that changes no number either), so the program holds one
Mamba-1 layer's text and not thirteen, and compiles in a fraction of the time.
"""

import itertools

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import banded_attention, gated_mlp
from benchmark.reference.olmoe import rms_norm

TOKEN_BLOCK = 128
ROW_BLOCK = 2048


def sequential_scan(x, dt, a, bmat, cmat, d_skip):
    """x, dt: [b, L, E]; a: [E, N]; bmat, cmat: [b, L, N]; d_skip: [E]. The
    recurrence token by token, ``TOKEN_BLOCK`` tokens a checkpoint."""
    b, length, e = x.shape
    block = TOKEN_BLOCK if length % TOKEN_BLOCK == 0 else length

    def token(state, row):
        x_t, dt_t, b_t, c_t = row
        state = (jnp.exp(dt_t[:, :, None] * a) * state
                 + (dt_t * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("ben,bn->be", state, c_t) + d_skip * x_t

    @jax.checkpoint
    def chunk(state, rows):
        return jax.lax.scan(token, state, rows)

    # [b, L, ...] -> [chunks, block, b, ...]
    rows = tuple(jnp.moveaxis(t, 1, 0).reshape(length // block, block, b, -1)
                 for t in (x, dt, bmat, cmat))
    _, y = jax.lax.scan(chunk, jnp.zeros((b, e, a.shape[1]), jnp.float32), rows)
    return jnp.moveaxis(y.reshape(length, b, e), 0, 1)


def mamba1(h, p, *, d_state, dt_rank, eps):
    length = h.shape[1]
    x, z = jnp.split(h @ p["in_proj"]["kernel"], 2, axis=-1)
    taps = p["conv"]                                      # [E, K]
    k = taps.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(taps[:, j] * padded[:, j:j + length]
                        for j in range(k)) + p["conv_bias"])
    rank, bmat, cmat = jnp.split(x @ p["x_proj"], [dt_rank, dt_rank + d_state],
                                 axis=-1)
    rank = rms_norm(rank, p["dt_norm"]["scale"], eps)
    bmat = rms_norm(bmat, p["b_norm"]["scale"], eps)
    cmat = rms_norm(cmat, p["c_norm"]["scale"], eps)
    dt = jax.nn.softplus(rank @ p["dt_proj"] + p["dt_bias"])
    y = sequential_scan(x, dt, -jnp.exp(p["A_log"]), bmat, cmat, p["D"])
    return (y * jax.nn.silu(z)) @ p["out_proj"]["kernel"]


def attention(h, p, *, n_heads, n_kv_heads):
    b, length, d = h.shape
    head_dim = d // n_heads
    heads = lambda t, n: t.reshape(b, length, n, head_dim)  # noqa: E731
    q = heads(h @ p["query"]["kernel"], n_heads)
    k = heads(h @ p["key"]["kernel"], n_kv_heads)
    v = heads(h @ p["value"]["kernel"], n_kv_heads)
    group = n_heads // n_kv_heads
    ctx = banded_attention(q, jnp.repeat(k, group, axis=2),
                           jnp.repeat(v, group, axis=2), None)
    return ctx.reshape(b, length, d) @ p["out"]["kernel"]


def blocked_mlp(x, p, eps):
    """``gated_mlp(RMSNorm(x))`` of every position, ``ROW_BLOCK`` positions at
    a time: the MLP reads one position, and 16,384 x 8,192 float32 gate and up
    products with their cotangents are 2 GB."""
    b, length, d = x.shape
    size = ROW_BLOCK if length % ROW_BLOCK == 0 else length

    @jax.checkpoint
    def one_block(rows):
        return gated_mlp(rms_norm(rows, p["ff_norm"]["scale"], eps), p["mlp"])

    blocks = x.reshape(b, length // size, size, d).swapaxes(0, 1)
    return jax.lax.map(one_block, blocks).swapaxes(0, 1).reshape(x.shape)


def block(p, x, *, is_attention, eps, ssm, attn):
    """The mixer and the MLP each under a ``jax.checkpoint`` of its own, so
    that a backward holds one of them at a time."""
    @jax.checkpoint
    def mixer(p, x):
        h = rms_norm(x, p["input_norm"]["scale"], eps)
        if is_attention:
            return attention(h, p["attn"], **attn)
        return mamba1(h, p["mamba"], eps=eps, **ssm)

    x = x + mixer(p, x)
    return x + jax.checkpoint(lambda p, x: blocked_mlp(x, p, eps))(p, x)


def blocked_mean_nll(x, table, targets):
    """Mean over every position of ``logsumexp(x E^T) - (x E^T)[target]``,
    ``ROW_BLOCK`` positions at a time."""
    rows, wanted = x.reshape(-1, x.shape[-1]), targets.reshape(-1)
    size = ROW_BLOCK if rows.shape[0] % ROW_BLOCK == 0 else rows.shape[0]

    @jax.checkpoint
    def one_block(args):
        h, t = args
        logits = h @ table.T
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1)
                       - jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0])

    sums = jax.lax.map(one_block, (rows.reshape(-1, size, rows.shape[-1]),
                                   wanted.reshape(-1, size)))
    return jnp.sum(sums) / rows.shape[0]


def loss(params, batch, *, n_layers: int, attn_period: int, attn_offset: int,
         d_state: int, dt_rank: int, n_heads: int, n_kv_heads: int,
         rms_eps: float):
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    table = params["embed"]["embedding"]
    x = table[inputs]
    kinds = dict(ssm=dict(d_state=d_state, dt_rank=dt_rank),
                 attn=dict(n_heads=n_heads, n_kv_heads=n_kv_heads))
    # a run of consecutive layers of one kind at a time
    for kind, run in itertools.groupby(
            range(n_layers), lambda i: i % attn_period == attn_offset):
        layer = jax.checkpoint(
            lambda p, x, kind=kind: block(p, x, eps=rms_eps, **kinds,
                                          is_attention=kind))
        stacked = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves),
            *[params[f"block_{i}"] for i in run])
        x, _ = jax.lax.scan(lambda x, p: (layer(p, x), None), x, stacked)
    x = rms_norm(x, params["final_norm"]["scale"], rms_eps)
    return blocked_mean_nll(x, table, targets)
