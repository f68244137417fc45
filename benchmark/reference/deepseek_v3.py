"""Plain float32 reference of the DeepSeek-V3 family's training loss with
latent attention (Kanana-2-30B-A3B), written from the published
``config.json`` and the family's published description, not from the
system's code: no kernel, no sort, no compaction, no bfloat16, no flax. The
parameter tree is read by name.

``x0 = E[tokens]``. Per layer: RMSNorm; the query projection, 32 heads of
``[q_nope | q_rope]``; the down projection to ``[c | k_rope]``, RMSNorm of the
latent ``c`` with its own weight; the up projection of ``c`` to a head's
``[k_nope | v]``; rotary embedding on ``q_rope`` a head and on the one
``k_rope``, over the pairs ``(2i, 2i + 1)`` at ``theta^(-2i/d_r)``, the
columns left in their order (the published code moves the even columns before
the odd ones and rotates halves: the same pairs and angles, another order of
the 64 columns in q and k alike, which no score sees); ``k = [k_nope |
k_rope]`` with ``k_rope`` repeated to every head; softmax attention at
``1/sqrt(d_n + d_r)`` over the keys ``j <= i``, as a mask; the output
projection, into the residual. Then RMSNorm and either the dense gated MLP or
``shared(h) + sum over the chosen experts`` (``reference/afmoe.py``
``mixture``: the same sigmoid scores, bias in the choice only, normalised and
scaled weights, one chip's share of the bank applied expert by expert, the
bias term whose gradient is the load error), into the residual. After the
last layer RMSNorm and the untied head; mean next-token cross-entropy.

Departures from the published description, all shared with the system: the
share of the experts (what the absent ones would add is left out) and of the
vocabulary; the bias rule carried as a loss term of zero value.

Attention is taken ``QUERY_BLOCK`` query rows at a time and each layer sits
under ``jax.checkpoint``, so that one sequence of 16,384 fits the chip beside
the parameters; neither changes a number.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import gated_mlp, mixture
from benchmark.reference.common import mean_nll
from benchmark.reference.olmoe import rms_norm

QUERY_BLOCK = 256


def rotary_pairs(x, theta):
    """x: [B, L, H, D]. Position m rotates the pair (x_2i, x_2i+1) by the
    angle m * theta^(-2i/D); the columns keep their places."""
    b, length, h, d = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.outer(jnp.arange(length, dtype=jnp.float32), freqs)
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    pairs = x.reshape(b, length, h, d // 2, 2)
    first, second = pairs[..., 0], pairs[..., 1]
    return jnp.stack([first * cos - second * sin, first * sin + second * cos],
                     axis=-1).reshape(b, length, h, d)


def causal_attention(q, k, v):
    """q, k: [B, L, H, Dk]; v: [B, L, H, Dv]. Softmax attention under the
    causal mask at 1/sqrt(Dk), ``QUERY_BLOCK`` queries at a time."""
    b, length, h, d = q.shape
    block = QUERY_BLOCK if length % QUERY_BLOCK == 0 else length
    keys = jnp.arange(length)[None, :]

    @jax.checkpoint
    def one_block(args):
        q_blk, first = args                              # [B, block, H, Dk]
        rows = first + jnp.arange(block)[:, None]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) / jnp.sqrt(
            jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(keys <= rows, scores, -1e9), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    blocks = q.reshape(b, length // block, block, h, d).swapaxes(0, 1)
    out = jax.lax.map(one_block, (blocks, jnp.arange(0, length, block)))
    return out.swapaxes(0, 1).reshape(b, length, h, v.shape[-1])


def latent_attention(h, a, *, n_heads, d_nope, d_rope, d_v, rank, eps, theta):
    b, length, _ = h.shape
    q = (h @ a["query"]["kernel"]).reshape(b, length, n_heads, d_nope + d_rope)
    down = h @ a["kv_down"]["kernel"]
    c = rms_norm(down[..., :rank], a["kv_norm"]["scale"], eps)
    kv = (c @ a["kv_up"]["kernel"]).reshape(b, length, n_heads, d_nope + d_v)
    q = jnp.concatenate(
        [q[..., :d_nope], rotary_pairs(q[..., d_nope:], theta)], axis=-1)
    k_rope = rotary_pairs(down[..., rank:][:, :, None, :], theta)
    k = jnp.concatenate(
        [kv[..., :d_nope], jnp.repeat(k_rope, n_heads, axis=2)], axis=-1)
    ctx = causal_attention(q, k, kv[..., d_nope:])
    return ctx.reshape(b, length, n_heads * d_v) @ a["out"]["kernel"]


def block(p, x, *, dense, eps, attn, route):
    b, length, d = x.shape
    x = x + latent_attention(rms_norm(x, p["ln_attn"]["scale"], eps), p["attn"],
                             eps=eps, **attn)
    h = rms_norm(x, p["ln_mlp"]["scale"], eps)
    if dense:
        return x + gated_mlp(h, p["mlp"]), 0.0
    y, bias_term = mixture(h.reshape(b * length, d), p["moe"], **route)
    return x + gated_mlp(h, p["moe"]["shared"]) + y.reshape(b, length, d), \
        bias_term


def loss(params, batch, *, n_heads: int, qk_nope_head_dim: int,
         qk_rope_head_dim: int, v_head_dim: int, kv_lora_rank: int,
         n_layers: int, n_dense_layers: int, top_k: int, rms_eps: float,
         rope_theta: float, route_norm: bool, route_scale: float,
         first_expert_held: int):
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"]["embedding"][inputs]
    attn = dict(n_heads=n_heads, d_nope=qk_nope_head_dim,
                d_rope=qk_rope_head_dim, d_v=v_head_dim, rank=kv_lora_rank,
                theta=rope_theta)
    route = dict(top_k=top_k, route_norm=route_norm, route_scale=route_scale,
                 first_expert_held=first_expert_held)
    bias_terms = 0.0
    for i in range(n_layers):
        x, term = jax.checkpoint(
            lambda p, x, dense=i < n_dense_layers: block(
                p, x, dense=dense, eps=rms_eps, attn=attn, route=route))(
            params[f"block_{i}"], x)
        bias_terms = bias_terms + term
    x = rms_norm(x, params["ln_f"]["scale"], rms_eps)
    return mean_nll(x @ params["lm_head"]["kernel"], targets) + bias_terms
