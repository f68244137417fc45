"""Plain float32 reference of LFM2-MoE's training loss, written from the
published ``config.json`` of ``LiquidAI/LFM2-24B-A2B`` and
``modeling_lfm2_moe.py``, not from the system's code: no kernel, no sort, no
compaction, no custom VJP, no bfloat16, no flax. The parameter tree is read
by name.

``x0 = E[tokens]``. Per layer, of kind ``layer_types[l]``: RMSNorm, then the
operator. A ``conv`` layer: one projection without bias to ``[B | C | u]``
(thirds, in this order), ``v = B * u``, the causal depthwise convolution
``c_t = sum_j w[:, j] * v_{t-(K-1)+j}`` as ``K`` shifted products on an array
padded with ``K - 1`` zero rows before each sequence, ``C * c``, the output
projection. A ``full_attention`` layer: q, k, v projections without bias;
RMSNorm of q and k per head over the head dim; rotary embedding in the
rotate-half form over the whole head; softmax attention at 1/sqrt(head_dim)
over the keys ``j <= i`` as a mask, query head ``n`` reading KV head ``n //
group`` (K and V repeated), a block of queries at a time; the output
projection. Into the residual. Then RMSNorm, and either the dense gated MLP or
the sum over the chosen experts: ``s = sigmoid(h.Wr)`` over the router's full
width, the ``top_k`` largest ``s + expert_bias`` chosen, the weights ``s`` of
the chosen divided by their sum (+ ``route_eps``) and times ``route_scale``;
no shared expert; into the residual. After the last layer RMSNorm and the
head, which is the embedding table; mean next-token cross-entropy.

**The share**, **the bias** and the blocks of queries are
``benchmark/reference/afmoe.py``'s, whose functions this file uses where the
mathematics is the same (``banded_attention`` without a window, ``gated_mlp``);
the mixture is written out again here because its normaliser differs and
nothing stands beside the routed sum.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import banded_attention, gated_mlp
from benchmark.reference.common import mean_nll
from benchmark.reference.olmoe import rms_norm, rotary


def short_conv(h, p):
    """h: [B, L, d] -> the conv operator's output [B, L, d]."""
    length = h.shape[1]
    taps = p["conv"]                                    # [d, K]
    b, c, u = jnp.split(h @ p["in_proj"]["kernel"], 3, axis=-1)
    k = taps.shape[1]
    v = jnp.pad(b * u, ((0, 0), (k - 1, 0), (0, 0)))    # zeros before position 0
    conv = sum(taps[:, j] * v[:, j:j + length] for j in range(k))
    return (c * conv) @ p["out_proj"]["kernel"]


def attention(h, a, *, n_heads, n_kv_heads, head_dim, eps, theta):
    b, length, _ = h.shape
    heads = lambda t, n: t.reshape(b, length, n, head_dim)  # noqa: E731
    q = rms_norm(heads(h @ a["query"]["kernel"], n_heads), a["q_norm"]["scale"], eps)
    k = rms_norm(heads(h @ a["key"]["kernel"], n_kv_heads), a["k_norm"]["scale"], eps)
    v = heads(h @ a["value"]["kernel"], n_kv_heads)
    q, k = rotary(q, theta), rotary(k, theta)
    group = n_heads // n_kv_heads
    ctx = banded_attention(q, jnp.repeat(k, group, axis=2),
                           jnp.repeat(v, group, axis=2), None)
    return ctx.reshape(b, length, n_heads * head_dim) @ a["out"]["kernel"]


def mixture(h, p, *, top_k, route_norm, route_scale, route_eps,
            first_expert_held):
    """h: [T, d] -> (the held experts' weighted sum, the bias term)."""
    scores = jax.nn.sigmoid(h @ p["router"])
    width = scores.shape[-1]
    choice = scores + jax.lax.stop_gradient(p["expert_bias"])
    kth = jnp.sort(choice, axis=-1)[:, width - top_k]
    chosen = choice >= kth[:, None]        # a tie at the k-th place has measure zero
    weights = jnp.where(chosen, scores, 0.0)
    if route_norm:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + route_eps)
    weights = weights * route_scale
    held = p["gate"].shape[0]
    y = jnp.zeros_like(h)
    for e in range(held):       # a dense [T] weight, zero where e was not chosen
        expert = jax.checkpoint(
            lambda gate, up, down, weight: weight[:, None]
            * ((jax.nn.silu(h @ gate) * (h @ up)) @ down))
        y = y + expert(p["gate"][e], p["up"][e], p["down"][e],
                       weights[:, first_expert_held + e])
    load = jax.lax.stop_gradient(jnp.sum(chosen.astype(jnp.float32), axis=0))
    bias = p["expert_bias"]
    bias_term = jnp.sum((bias - jax.lax.stop_gradient(bias))
                        * (load - load.mean())) / h.shape[0]
    return y, bias_term


def block(p, x, *, kind, dense, attn, eps, route):
    b, length, d = x.shape
    h = rms_norm(x, p["operator_norm"]["scale"], eps)
    if kind == "conv":
        x = x + short_conv(h, p["conv"])
    else:
        x = x + attention(h, p["attn"], eps=eps, **attn)
    h = rms_norm(x, p["ffn_norm"]["scale"], eps)
    if dense:
        return x + gated_mlp(h, p["mlp"]), 0.0
    y, bias_term = mixture(h.reshape(b * length, d), p["moe"], **route)
    return x + y.reshape(b, length, d), bias_term


def loss(params, batch, *, n_heads: int, n_kv_heads: int, head_dim: int,
         layer_types, n_dense_layers: int, top_k: int, rms_eps: float,
         rope_theta: float, route_norm: bool, route_scale: float,
         route_eps: float, first_expert_held: int):
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    table = params["embed"]["embedding"]
    x = table[inputs]
    attn = dict(n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
                theta=rope_theta)
    route = dict(top_k=top_k, route_norm=route_norm, route_scale=route_scale,
                 route_eps=route_eps, first_expert_held=first_expert_held)
    bias_terms = 0.0
    for i, kind in enumerate(layer_types):
        # a layer at a time under jax.checkpoint, so that 8,192 positions fit
        # the chip beside the parameters; it changes no number
        x, term = jax.checkpoint(
            lambda p, x, kind=kind, dense=i < n_dense_layers: block(
                p, x, kind=kind, dense=dense, attn=attn, eps=rms_eps,
                route=route))(params[f"block_{i}"], x)
        bias_terms = bias_terms + term
    x = rms_norm(x, params["embedding_norm"]["scale"], rms_eps)
    return mean_nll(x @ table.T, targets) + bias_terms
