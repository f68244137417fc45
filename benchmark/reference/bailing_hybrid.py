"""Plain float32 reference of the Ling / Ring hybrid family's training loss
(Ling-3.0-flash, ``model_type`` ``bailing_hybrid``), written from the
published ``config.json``, Kimi Linear's paper (arXiv:2510.26692) and the
families' published descriptions, not from the system's code: no kernel, no
chunked form, no sort, no compaction, no bfloat16, no flax. The parameter tree
is read by name.

``x0 = E[tokens]``. Per layer ``i``: RMSNorm (eps 1e-6); the mixer, into the
residual; RMSNorm; the dense gated-SiLU MLP or the mixture, into the residual.
After the last layer RMSNorm and the untied head; mean next-token
cross-entropy.

**Kimi Delta Attention** (``layer_types[i] == "kda"``; ``H`` heads of ``D =
128``, no bias)::

    q = silu(conv4(h W_q)),  k = silu(conv4(h W_k)),  v = silu(conv4(h W_v))
        conv4: y_t = sum_j w[:, j] x_{t - 3 + j}, a channel each, zeros before the sequence
    q = q / sqrt(sum_head(q^2) + 1e-6) * D^-0.5;   k = k / sqrt(sum_head(k^2) + 1e-6)
    g_t = lower_bound * sigmoid(exp(A_log_h) * (h_t W_f + dt_bias))     a channel, in (-5, 0)
    beta_t = sigmoid(h_t W_beta)                                        a head
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T,   S_0 = 0
    o_t = S_t^T q_t
    y_t = W_o (RMSNorm_head(o_t) * sigmoid(h_t W_g))     one [D] weight for every head's norm

The recurrence is **the recurrence itself, a token at a time**
(:func:`delta_rule`: ``lax.scan`` over ``t`` with the ``[H, D, D]`` state), not
the chunked form the system runs, so that the WY algebra is checked against
something that does not contain it. So that its gradients fit (8,192 states of
1 MiB a layer would not) the scan is nested: outer steps of ``TOKEN_BLOCK``
tokens, each under ``jax.checkpoint``; no arithmetic changes.

**Latent attention** (``"mla"``): ``reference/deepseek_v3.py``'s equations (no
query low-rank path, the latent's own norm, rotary pairs on the last 64 of a
key's 192 columns, one rotary key for every head, attention in blocks of
queries over explicit score planes), then each head's output times
``sigmoid(h W_gate)`` of its own (``W_gate [d, H]``) before the output
projection.

**The mixture**: ``s = sigmoid(h.Wr)`` over the router's full width; with the
bias ``b`` (in the choice only): a group's score is the sum of its two largest
``s + b`` among its ``E / n_group`` experts (``top_k`` of 2), the
``topk_group`` best groups stay (``top_k``), the ``top_k`` largest ``s + b``
among their experts are chosen (``top_k``); weights ``s / (sum of the chosen s
+ 1e-20) * route_scale``; ``shared(h) + sum over the chosen``. The share of the
bank and the bias term are ``reference/afmoe.py``'s.

**The shares.** ``n_heads`` is the number of heads *held*: every projection
in the tree is the held heads', and what the other heads would add to
``W_o``'s sum is left out, here as in the system; with every head held it is
the uncut layer. Likewise the experts and the vocabulary.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import gated_mlp
from benchmark.reference.common import mean_nll
from benchmark.reference.deepseek_v3 import causal_attention, rotary_pairs
from benchmark.reference.olmoe import rms_norm

TOKEN_BLOCK = 64


def conv_silu(x, taps):
    """x: [B, L, C]; taps: [C, K]. ``silu(sum_j taps[:, j] x_{t - (K-1) + j})``."""
    k = taps.shape[1]
    length = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + length] * taps[:, j]
                           for j in range(k)))


def delta_rule(q, k, v, g, beta):
    """q, k, v, g: [B, L, H, D]; beta: [B, L, H]. The gated delta rule a token
    at a time: ``(o [B, L, H, D], the last state [B, H, D, D])``."""
    b, length, h, d = q.shape

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x                   # [B, H, D] ..., [B, H]
        state = jnp.exp(g_t)[..., None] * state
        error = v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + (beta_t[..., None, None] * k_t[..., None]
                         * error[..., None, :])
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    block = TOKEN_BLOCK if length % TOKEN_BLOCK == 0 else length
    by_block = lambda t: jnp.moveaxis(t, 1, 0).reshape(  # noqa: E731
        length // block, block, *t.shape[:1], *t.shape[2:])
    state, o = jax.lax.scan(tokens, jnp.zeros((b, h, d, v.shape[-1])),
                            tuple(by_block(t) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape(length, b, h, v.shape[-1]), 0, 1), state


def kimi_delta_attention(h, a, *, n_heads, head_dim, lower_bound, eps):
    b, length, _ = h.shape
    heads = lambda t: t.reshape(b, length, n_heads, head_dim)  # noqa: E731
    q, k, v = (heads(conv_silu(h @ a[name]["kernel"], a[f"{name}_conv"]))
               for name in ("query", "key", "value"))
    unit = lambda t: t * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
    q, k = unit(q) * head_dim ** -0.5, unit(k)
    speed = jnp.exp(a["A_log"])[:, None]                                  # [H, 1]
    g = lower_bound * jax.nn.sigmoid(
        speed * heads(h @ a["decay"] + a["dt_bias"]))
    beta = jax.nn.sigmoid(h @ a["beta"])
    o, _ = delta_rule(q, k, v, g, beta)
    y = rms_norm(o, a["out_norm"], eps) * jax.nn.sigmoid(
        heads(h @ a["gate"]["kernel"]))
    return y.reshape(b, length, n_heads * head_dim) @ a["out"]["kernel"]


def gated_latent_attention(h, a, *, n_heads, d_nope, d_rope, d_v, rank, eps,
                           theta):
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
    ctx = ctx * jax.nn.sigmoid(h @ a["gate"]["kernel"])[..., None]
    return ctx.reshape(b, length, n_heads * d_v) @ a["out"]["kernel"]


def grouped_choice(choice, top_k: int, n_group: int, topk_group: int):
    """``[T, E]`` of bools: the ``top_k`` largest entries of each row among
    the ``topk_group`` groups whose two largest entries sum highest."""
    tokens, width = choice.shape
    groups = choice.reshape(tokens, n_group, width // n_group)
    group_scores = jax.lax.top_k(groups, 2)[0].sum(axis=-1)
    _, kept = jax.lax.top_k(group_scores, topk_group)
    stays = jax.nn.one_hot(kept, n_group).sum(axis=1) > 0                 # [T, n_group]
    among = jnp.where(jnp.repeat(stays, width // n_group, axis=1), choice,
                      -jnp.inf)
    _, chosen = jax.lax.top_k(among, top_k)
    return jax.nn.one_hot(chosen, width).sum(axis=1) > 0


def mixture(h, p, *, top_k, n_group, topk_group, route_norm, route_scale,
            first_expert_held):
    """h: [T, d] -> (the held experts' weighted sum, the bias term)."""
    scores = jax.nn.sigmoid(h @ p["router"])
    chosen = grouped_choice(scores + jax.lax.stop_gradient(p["expert_bias"]),
                            top_k, n_group, topk_group)
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


def block(p, x, *, kind, dense, eps, kda, attn, route):
    b, length, d = x.shape
    h = rms_norm(x, p["ln_attn"]["scale"], eps)
    if kind == "mla":
        x = x + gated_latent_attention(h, p["attn"], eps=eps, **attn)
    else:
        x = x + kimi_delta_attention(h, p["kda"], eps=eps, **kda)
    h = rms_norm(x, p["ln_mlp"]["scale"], eps)
    if dense:
        return x + gated_mlp(h, p["mlp"]), 0.0
    y, bias_term = mixture(h.reshape(b * length, d), p["moe"], **route)
    return x + gated_mlp(h, p["moe"]["shared"]) + y.reshape(b, length, d), \
        bias_term


def loss(params, batch, *, layer_types, n_dense_layers: int, n_heads: int,
         head_dim: int, kda_lower_bound: float, qk_nope_head_dim: int,
         qk_rope_head_dim: int, v_head_dim: int, kv_lora_rank: int, top_k: int,
         n_group: int, topk_group: int, rms_eps: float, rope_theta: float,
         route_norm: bool, route_scale: float, first_expert_held: int):
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"]["embedding"][inputs]
    kda = dict(n_heads=n_heads, head_dim=head_dim, lower_bound=kda_lower_bound)
    attn = dict(n_heads=n_heads, d_nope=qk_nope_head_dim,
                d_rope=qk_rope_head_dim, d_v=v_head_dim, rank=kv_lora_rank,
                theta=rope_theta)
    route = dict(top_k=top_k, n_group=n_group, topk_group=topk_group,
                 route_norm=route_norm, route_scale=route_scale,
                 first_expert_held=first_expert_held)
    bias_terms = 0.0
    for i, kind in enumerate(layer_types):
        x, term = jax.checkpoint(
            lambda p, x, kind=kind, dense=i < n_dense_layers: block(
                p, x, kind=kind, dense=dense, eps=rms_eps, kda=kda, attn=attn,
                route=route))(params[f"block_{i}"], x)
        bias_terms = bias_terms + term
    x = rms_norm(x, params["ln_f"]["scale"], rms_eps)
    return mean_nll(x @ params["lm_head"]["kernel"], targets) + bias_terms
