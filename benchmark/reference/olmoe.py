"""Plain float32 reference of OLMoE's training loss, written from the
published description (Muennighoff et al. 2024, arXiv:2409.02060, and the
public ``modeling_olmoe.py``), not from the system's code: no sort, no
grouped product, no kernel, no bfloat16, no flax. The parameter tree is read
by name.

Per layer: RMSNorm; q, k, v projections without bias; RMSNorm over the whole
q and k projections before the split into heads; rotary embedding in the
rotate-half form over the whole head dim; causal softmax attention at
1/sqrt(head_dim) (``reference/common.attention``); the output projection into
the residual; RMSNorm; a softmax router over all experts; the ``top_k``
largest probabilities kept as they are (``norm_topk_prob`` false); every
expert applied to every token, one expert a scan step under
``jax.checkpoint``, and its output weighted by the token's probability for it
where the expert is among the token's ``top_k``, by zero elsewhere. After the
last layer RMSNorm and the untied head.

Loss: mean next-token cross-entropy + ``load_balance_weight`` x the
load-balancing loss ``E * sum_e f_e P_e`` (``f_e``: slots routed to expert
``e`` over the tokens, constant under differentiation; ``P_e``: its mean
probability) + ``router_z_weight`` x ``mean(logsumexp(router logits)^2)``,
both means over the layers.

Departures from the published model, shared with the system and listed in the
configuration file: the two auxiliary weights are the paper's (config.json
carries neither), the load-balancing loss is computed a layer at a time over
the micro-batch and averaged over the layers (the published code concatenates
the layers' router logits first: the same number at equal token counts),
dropout 0."""

import jax
import jax.numpy as jnp

from benchmark.reference.common import attention, mean_nll


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """x: [B, L, H, D]. Position m rotates the pair (x_i, x_{i+D/2}) by the
    angle m * theta^(-2i/D)."""
    length, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.outer(jnp.arange(length, dtype=jnp.float32), freqs)
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    half = d // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(angle) + turned * jnp.sin(angle)


def mixture(h, p, top_k):
    """h: [T, d] -> (sum over each token's top_k experts of probability x
    expert(h), load-balancing loss, z-loss)."""
    logits = h @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    n_experts = probs.shape[-1]
    kth = jnp.sort(probs, axis=-1)[:, n_experts - top_k]
    chosen = probs >= kth[:, None]          # a tie at the k-th place has measure zero
    gates = jnp.where(chosen, probs, 0.0)

    @jax.checkpoint
    def one_expert(gate, up, down, weight):
        return weight[:, None] * ((jax.nn.silu(h @ gate) * (h @ up)) @ down)

    y, _ = jax.lax.scan(lambda total, e: (total + one_expert(*e), None),
                        jnp.zeros_like(h),
                        (p["gate"], p["up"], p["down"], gates.T))
    share = jax.lax.stop_gradient(jnp.mean(chosen.astype(jnp.float32), axis=0))
    balance = n_experts * jnp.sum(share * jnp.mean(probs, axis=0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return y, balance, z


def block(p, x, mask, *, n_heads, top_k, eps, theta):
    b, length, d = x.shape
    h = rms_norm(x, p["ln_attn"]["scale"], eps)
    a = p["attn"]
    q = rms_norm(h @ a["query"]["kernel"], a["q_norm"]["scale"], eps)
    k = rms_norm(h @ a["key"]["kernel"], a["k_norm"]["scale"], eps)
    v = h @ a["value"]["kernel"]
    split = lambda t: t.reshape(b, length, n_heads, d // n_heads)  # noqa: E731
    ctx = attention(rotary(split(q), theta), rotary(split(k), theta), split(v),
                    mask)
    x = x + ctx.reshape(b, length, d) @ a["out"]["kernel"]
    h = rms_norm(x, p["ln_moe"]["scale"], eps)
    y, balance, z = mixture(h.reshape(b * length, d), p["moe"], top_k)
    return x + y.reshape(b, length, d), balance, z


def loss(params, batch, *, n_heads: int, n_layers: int, top_k: int,
         rms_eps: float, rope_theta: float, load_balance_weight: float,
         router_z_weight: float):
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    length = inputs.shape[1]
    x = params["embed"]["embedding"][inputs]
    causal = jnp.tril(jnp.ones((length, length), bool))
    mask = jnp.where(causal, 0.0, -1e9).astype(jnp.float32)
    balance = z = 0.0
    for i in range(n_layers):
        x, b_i, z_i = jax.checkpoint(
            lambda p, x: block(p, x, mask, n_heads=n_heads, top_k=top_k,
                               eps=rms_eps, theta=rope_theta))(
            params[f"block_{i}"], x)
        balance, z = balance + b_i / n_layers, z + z_i / n_layers
    x = rms_norm(x, params["ln_f"]["scale"], rms_eps)
    nll = mean_nll(x @ params["lm_head"]["kernel"], targets)
    return nll + load_balance_weight * balance + router_z_weight * z
