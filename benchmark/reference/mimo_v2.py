"""Plain float32 reference of MiMo-V2's training loss, written from the
published ``config.json`` of ``XiaomiMiMo/MiMo-V2.5`` (``model_type``
``mimo_v2``), not from the system's code: no kernel, no sort, no compaction,
no bfloat16, no flax, no import from ``autodist_tpu``. The parameter tree is
read by name.

``x0 = E[tokens]``. Per layer, of kind ``layer_pattern[l]`` (0 full, 1
sliding): RMSNorm; q ``[d, H hd]``, k ``[d, H_kv hd]``, v ``[d, H_kv vd]``
without bias or norm, ``H_kv`` and the rotary base the layer kind's; ``v``
times ``value_scale``; the first ``rotary_dim`` columns of every head of q
and k turned in the rotate-half form, the rest passed; softmax attention at
``1 / sqrt(hd)`` over the keys ``j <= i`` and, on a sliding layer, ``i - j <
window``, as a mask, query head ``n`` reading KV head ``n // group`` (K and V
repeated); **on a sliding layer the head's sink is one more column of the
logits, concatenated, and its probability is dropped** (it has no value); the
output projection into the residual. Then RMSNorm and either the dense gated
MLP (``dense[l]``) or the sum over the chosen experts: ``s = sigmoid(h.Wr)``
over the router's full width, the ``top_k`` largest ``s + expert_bias``
chosen, the weights ``s`` of the chosen divided by their sum (+ 1e-20); into
the residual. After the last layer RMSNorm and the untied head; mean
next-token cross-entropy.

**The share** is a mask over the router's outputs and **the bias** a loss
term linear in ``expert_bias`` and zero in value, both ``reference/afmoe.py``'s
``mixture`` (at ``route_scale`` 1): each held expert is applied to every
token, one a scan step, weighted by the token's weight for it where it is
among the token's ``top_k`` and by zero elsewhere; what the absent experts
would add is left out, here as in the system. With the whole bank this is
the uncut layer. The norm, the gated MLP and the blocked head's loss are
their sibling references' too (``olmoe.py``, ``afmoe.py``, ``jamba.py``).

Blocking that changes no number: attention a block of ``QUERY_BLOCK`` queries
at a time under ``jax.checkpoint`` (64 heads x 8,192 x 8,192 float32 scores
never exist at once), the dense MLP and the head ``ROW_BLOCK`` positions at a
time, each layer under ``jax.checkpoint``, and a run of consecutive layers of
one kind as one ``lax.scan`` over their stacked parameters (the five sliding
expert layers are one layer's text in the program).
"""

import itertools

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import gated_mlp, mixture
from benchmark.reference.jamba import blocked_mean_nll
from benchmark.reference.olmoe import rms_norm

QUERY_BLOCK = 256
ROW_BLOCK = 2048


def partial_rotary(x, theta, rotary_dim):
    """x: [B, L, H, D]. Position m rotates the pair (x_i, x_{i + R/2}), i <
    R/2, of the first R = ``rotary_dim`` columns by m * theta^(-2i/R); the
    columns from R on pass."""
    length, half = x.shape[1], rotary_dim // 2
    freqs = 1.0 / theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                            / rotary_dim)
    angle = jnp.outer(jnp.arange(length, dtype=jnp.float32), freqs)
    cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    a, b, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def sink_attention(q, k, v, window, sink):
    """q: [B, L, H, hd]; k: [B, L, H, hd], v: [B, L, H, vd] (already
    repeated); sink: [H] or None. Softmax attention under the causal band
    with the sink as a concatenated column, ``QUERY_BLOCK`` queries at a
    time."""
    b, length, h, d = q.shape
    block = QUERY_BLOCK if length % QUERY_BLOCK == 0 else length
    keys = jnp.arange(length)[None, :]

    @jax.checkpoint
    def one_block(args):
        q_blk, first = args                              # [B, block, H, hd]
        rows = first + jnp.arange(block)[:, None]
        visible = keys <= rows
        if window is not None:
            visible &= rows - keys < window
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) / jnp.sqrt(
            jnp.float32(d))
        scores = jnp.where(visible, scores, -1e9)
        if sink is not None:
            column = jnp.broadcast_to(sink[None, :, None, None],
                                      (b, h, block, 1))
            scores = jnp.concatenate([scores, column], axis=-1)
        probs = jax.nn.softmax(scores, axis=-1)[..., :length]
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    blocks = q.reshape(b, length // block, block, h, d).swapaxes(0, 1)
    out = jax.lax.map(one_block, (blocks, jnp.arange(0, length, block)))
    return out.swapaxes(0, 1).reshape(b, length, h, v.shape[-1])


def blocked_mlp(h, p):
    """``gated_mlp`` of every position, ``ROW_BLOCK`` positions at a time."""
    b, length, d = h.shape
    size = ROW_BLOCK if length % ROW_BLOCK == 0 else length
    blocks = h.reshape(b, length // size, size, d).swapaxes(0, 1)
    out = jax.lax.map(jax.checkpoint(lambda rows: gated_mlp(rows, p)), blocks)
    return out.swapaxes(0, 1).reshape(h.shape)


def attention(h, p, *, sliding, n_heads, head_dim, v_head_dim, window, theta,
              rotary_dim, value_scale):
    b, length, _ = h.shape
    q = (h @ p["query"]["kernel"]).reshape(b, length, n_heads, head_dim)
    k = (h @ p["key"]["kernel"]).reshape(b, length, -1, head_dim)
    v = (h @ p["value"]["kernel"]).reshape(b, length, -1, v_head_dim) * value_scale
    q, k = (partial_rotary(t, theta, rotary_dim) for t in (q, k))
    group = n_heads // k.shape[2]
    ctx = sink_attention(q, jnp.repeat(k, group, axis=2),
                         jnp.repeat(v, group, axis=2),
                         window if sliding else None,
                         p["sink"] if sliding else None)
    return ctx.reshape(b, length, n_heads * v_head_dim) @ p["out"]["kernel"]


def block(p, x, *, sliding, dense, eps, attn, route):
    """The attention and the feed-forward each under a ``jax.checkpoint`` of
    its own, so that a backward holds one of them at a time."""
    b, length, d = x.shape
    x = x + jax.checkpoint(lambda p, x: attention(
        rms_norm(x, p["ln_in"]["scale"], eps), p["attn"], sliding=sliding,
        **attn))(p, x)

    @jax.checkpoint
    def feed_forward(p, x):
        h = rms_norm(x, p["ln_post"]["scale"], eps)
        if dense:
            return blocked_mlp(h, p["mlp"]), jnp.zeros((), jnp.float32)
        y, bias_term = mixture(h.reshape(b * length, d), p["moe"], **route)
        return y.reshape(b, length, d), bias_term

    m, bias_term = feed_forward(p, x)
    return x + m, bias_term


def loss(params, batch, *, layer_pattern, dense, n_heads: int, head_dim: int,
         v_head_dim: int, window: int, rotary_dim: int, rope_theta: float,
         swa_rope_theta: float, value_scale: float, top_k: int,
         rms_eps: float, first_expert_held: int):
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"]["embedding"][inputs]
    # norm_topk_prob; routed_scaling_factor null: 1
    route = dict(top_k=top_k, route_norm=True, route_scale=1.0,
                 first_expert_held=first_expert_held)
    bias_terms = 0.0
    # a run of consecutive layers of one kind at a time
    kinds = list(zip(layer_pattern, dense))
    for (kind, is_dense), run in itertools.groupby(
            range(len(kinds)), lambda i: kinds[i]):
        sliding = kind == 1
        attn = dict(n_heads=n_heads, head_dim=head_dim, v_head_dim=v_head_dim,
                    window=window, rotary_dim=rotary_dim, value_scale=value_scale,
                    theta=swa_rope_theta if sliding else rope_theta)
        layer = jax.checkpoint(
            lambda p, x, sliding=sliding, is_dense=is_dense, attn=attn: block(
                p, x, sliding=sliding, dense=is_dense, eps=rms_eps, attn=attn,
                route=route))
        stacked = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves),
            *[params[f"block_{i}"] for i in run])
        x, terms = jax.lax.scan(lambda x, p: layer(p, x), x, stacked)
        bias_terms = bias_terms + jnp.sum(terms)
    x = rms_norm(x, params["ln_f"]["scale"], rms_eps)
    return blocked_mean_nll(x, params["lm_head"]["kernel"].T, targets) + bias_terms
