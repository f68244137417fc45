"""Plain float32 reference of AFMoE's (Trinity's) training loss, written from
the published ``config.json`` and ``modeling_afmoe.py`` of
``arcee-ai/Trinity-Mini``, not from the system's code: no kernel, no sort, no
compaction, no bfloat16, no flax. The parameter tree is read by name.

``x0 = E[tokens] * sqrt(d)`` (``mup_enabled``). Per layer, of kind
``layer_types[l]``: RMSNorm; q, k, v and gate projections without bias;
RMSNorm of q and k per head over the head dim; on a sliding layer rotary
embedding in the rotate-half form over the whole head (a full layer has no
position signal); softmax attention at 1/sqrt(head_dim) over the keys ``j <=
i`` and, on a sliding layer, ``i - j < window``, as a mask, query head ``n``
reading KV head ``n // group`` (K and V repeated); the result times
``sigmoid(gate)``; the output projection, RMSNorm, into the residual. Then
RMSNorm, and either the dense gated MLP or ``shared(h) + sum over the chosen
experts``: ``s = sigmoid(h.Wr)`` over the router's full width, the ``top_k``
largest ``s + expert_bias`` chosen, the weights ``s`` of the chosen divided
by their sum (+ 1e-20) and times ``route_scale``; RMSNorm, into the residual.
After the last layer RMSNorm and the untied head; mean next-token
cross-entropy.

**The share.** The bank holds the experts ``[first_expert_held,
first_expert_held + held)`` of the router's width. Each held expert is
applied to every token, one a scan step, and weighted by the token's weight
for it where it is among the token's ``top_k``, by zero elsewhere; what the
absent experts would add is left out, here as in the system. With the whole
bank (``held`` = the router's width) this is the uncut layer.

**The bias.** The published rule moves ``expert_bias`` after each step by the
sign of the load error, without a gradient. The system carries it as a loss
term linear in the bias and zero in value, ``sum_e (b_e - stop_gradient(b_e))
. stop_gradient(c_e - mean c) / T`` a layer (``c_e``: the rows expert ``e``
received), and so does this file: the loss is the cross-entropy, and its
gradient with respect to the bias is the load error, which the check
compares like any other leaf.

Attention is taken a query block at a time and each layer sits under
``jax.checkpoint``, so that one sequence of 8,192 fits the chip beside the
parameters; neither changes a number.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.common import mean_nll
from benchmark.reference.olmoe import rms_norm, rotary

QUERY_BLOCK = 512


def banded_attention(q, k, v, window):
    """q: [B, L, H, D]; k, v: [B, L, H, D] (already repeated). Softmax
    attention under the causal band, ``QUERY_BLOCK`` queries at a time."""
    b, length, h, d = q.shape
    block = QUERY_BLOCK if length % QUERY_BLOCK == 0 else length
    keys = jnp.arange(length)[None, :]

    @jax.checkpoint
    def one_block(args):
        q_blk, first = args                              # [B, block, H, D]
        rows = first + jnp.arange(block)[:, None]
        visible = keys <= rows
        if window is not None:
            visible &= rows - keys < window
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) / jnp.sqrt(
            jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(visible, scores, -1e9), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    blocks = q.reshape(b, length // block, block, h, d).swapaxes(0, 1)
    out = jax.lax.map(one_block,
                      (blocks, jnp.arange(0, length, block)))
    return out.swapaxes(0, 1).reshape(b, length, h, d)


def gated_mlp(h, p):
    return (jax.nn.silu(h @ p["gate"]["kernel"]) * (h @ p["up"]["kernel"])) \
        @ p["down"]["kernel"]


def mixture(h, p, *, top_k, route_norm, route_scale, first_expert_held):
    """h: [T, d] -> (the held experts' weighted sum, the bias term)."""
    scores = jax.nn.sigmoid(h @ p["router"])
    width = scores.shape[-1]
    choice = scores + jax.lax.stop_gradient(p["expert_bias"])
    kth = jnp.sort(choice, axis=-1)[:, width - top_k]
    chosen = choice >= kth[:, None]        # a tie at the k-th place has measure zero
    weights = jnp.where(chosen, scores, 0.0)
    if route_norm:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    weights = weights * route_scale
    held = p["gate"].shape[0]
    mine = weights[:, first_expert_held:first_expert_held + held]

    @jax.checkpoint
    def one_expert(gate, up, down, weight):
        return weight[:, None] * ((jax.nn.silu(h @ gate) * (h @ up)) @ down)

    y, _ = jax.lax.scan(lambda total, e: (total + one_expert(*e), None),
                        jnp.zeros_like(h),
                        (p["gate"], p["up"], p["down"], mine.T))
    load = jax.lax.stop_gradient(jnp.sum(chosen.astype(jnp.float32), axis=0))
    bias = p["expert_bias"]
    bias_term = jnp.sum((bias - jax.lax.stop_gradient(bias))
                        * (load - load.mean())) / h.shape[0]
    return y, bias_term


def block(p, x, *, kind, dense, n_heads, n_kv_heads, head_dim, window, eps,
          theta, route):
    b, length, d = x.shape
    h = rms_norm(x, p["ln_in"]["scale"], eps)
    a = p["attn"]
    heads = lambda t, n: t.reshape(b, length, n, head_dim)  # noqa: E731
    q = rms_norm(heads(h @ a["query"]["kernel"], n_heads), a["q_norm"]["scale"], eps)
    k = rms_norm(heads(h @ a["key"]["kernel"], n_kv_heads), a["k_norm"]["scale"], eps)
    v = heads(h @ a["value"]["kernel"], n_kv_heads)
    sliding = kind == "sliding_attention"
    if sliding:
        q, k = rotary(q, theta), rotary(k, theta)
    group = n_heads // n_kv_heads
    ctx = banded_attention(q, jnp.repeat(k, group, axis=2),
                           jnp.repeat(v, group, axis=2),
                           window if sliding else None)
    ctx = ctx.reshape(b, length, n_heads * head_dim) \
        * jax.nn.sigmoid(h @ a["gate"]["kernel"])
    x = x + rms_norm(ctx @ a["out"]["kernel"], p["ln_post_attn"]["scale"], eps)
    h = rms_norm(x, p["ln_pre_mlp"]["scale"], eps)
    if dense:
        m, bias_term = gated_mlp(h, p["mlp"]), 0.0
    else:
        y, bias_term = mixture(h.reshape(b * length, d), p["moe"], **route)
        m = gated_mlp(h, p["moe"]["shared"]) + y.reshape(b, length, d)
    return x + rms_norm(m, p["ln_post_mlp"]["scale"], eps), bias_term


def loss(params, batch, *, n_heads: int, n_kv_heads: int, head_dim: int,
         layer_types, n_dense_layers: int, top_k: int, window: int,
         rms_eps: float, rope_theta: float, route_norm: bool,
         route_scale: float, mup_enabled: bool, first_expert_held: int):
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"]["embedding"][inputs]
    if mup_enabled:
        x = x * jnp.sqrt(jnp.float32(x.shape[-1]))
    route = dict(top_k=top_k, route_norm=route_norm, route_scale=route_scale,
                 first_expert_held=first_expert_held)
    bias_terms = 0.0
    for i, kind in enumerate(layer_types):
        x, term = jax.checkpoint(
            lambda p, x, kind=kind, dense=i < n_dense_layers: block(
                p, x, kind=kind, dense=dense, n_heads=n_heads,
                n_kv_heads=n_kv_heads, head_dim=head_dim, window=window,
                eps=rms_eps, theta=rope_theta, route=route))(
            params[f"block_{i}"], x)
        bias_terms = bias_terms + term
    x = rms_norm(x, params["ln_f"]["scale"], rms_eps)
    return mean_nll(x @ params["lm_head"]["kernel"], targets) + bias_terms
