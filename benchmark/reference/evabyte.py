"""Plain float32 reference of EvaByte's training loss, written from the
published ``config.json`` and the equations below, not from the system's
code: no kernel, no online softmax, no bfloat16, no flax. The parameter tree
is read by name. The caller traces it under
``jax.default_matmul_precision("highest")``.

``config.json`` fixes the widths and the names ``eva``, ``window_size``,
``chunk_size``, ``num_pred_heads``; what goes beyond that is the model's
public code and the EVA paper (Zheng et al., ICLR 2023, the deterministic
form) as this repository's authors know them (``assumed`` in
``benchmark/configs/evabyte-6.5b.json``)::

    x = E[tokens]
    per layer:  x = x + Attn(norm1(x));  x = x + MLP(norm2(x))
    norm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)
    MLP(h)  = W_down (silu(W_gate h) * (W_up h))
    Attn(h): q, k, v = h W_q, h W_k, h W_v to [L, H, 128], no bias; rotary embedding on q and
      k over the whole head width, the pair (x_i, x_{i + 64}) turned by position * theta^(-2i/128);
      s = 128^-0.5; window w(i) = i // 2,048, chunk c = positions [16 c, 16 c + 16)
      a chunk's summary, from its 16 rotated keys and its values, with the head's phi, mu [128]:
        a_cj = softmax_j(s * k_cj . phi);  k~_c = sum_j a_cj k_cj + mu;  v~_c = sum_j a_cj v_cj
      query i sees the positions j of its own window with j <= i at logits s * q_i . k_j and every
      chunk c of every EARLIER window at logits s * q_i . k~_c, under one softmax; the output is
      the weighted sum of the v_j and the v~_c;  o = concat_heads(...) W_o
    logits = norm_f(x) W_head, W_head [d, 8 x 320]: the columns [320 j, 320 (j + 1)) are head j's
    loss = the mean over the eight heads of each head's mean cross-entropy over its own valid
           positions: head j at position t against token t + 1 + j where the batch has one

Departure from the published model, shared with the system: **the share of
the heads**. The parameter tree holds the projections of the heads this chip
holds and nothing of the others; what their part of ``W_o`` would add is left
out, and that partial result goes on to the MLP.

Attention is taken one window at a time over an explicit ``[window, (earlier
windows' chunks) + window]`` score plane and a plain softmax, and each window
and each layer sits under ``jax.checkpoint``, so that one sequence of 16,384
fits the chip beside the system's gradients at 620M parameters; neither
changes a number.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import gated_mlp
from benchmark.reference.common import mean_nll
from benchmark.reference.olmoe import rotary


def unit_offset_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def summaries(k, v, phi, mu, chunk):
    """k, v: [B, L, H, D]; phi, mu: [H, D] -> (k~, v~) [B, L / chunk, H, D]."""
    b, length, h, d = k.shape
    kc = k.reshape(b, length // chunk, chunk, h, d)
    vc = v.reshape(b, length // chunk, chunk, h, d)
    a = jax.nn.softmax(jnp.einsum("bcjhd,hd->bcjh", kc, phi) / jnp.sqrt(
        jnp.float32(d)), axis=2)
    return (jnp.einsum("bcjh,bcjhd->bchd", a, kc) + mu,
            jnp.einsum("bcjh,bcjhd->bchd", a, vc))


def eva_attention(q, k, v, phi, mu, *, window, chunk):
    """q, k (rotated), v: [B, L, H, D]. One window at a time: the window's
    queries against [the summaries of every earlier window | the window's
    own keys under the causal mask], one softmax."""
    length, d = q.shape[1], q.shape[-1]
    ks, vs = summaries(k, v, phi, mu, chunk)
    causal = jnp.tril(jnp.ones((window, window), bool))

    @jax.checkpoint
    def one_window(q_w, k_w, v_w, ks_seen, vs_seen):
        keys = jnp.concatenate([ks_seen, k_w], axis=1)
        values = jnp.concatenate([vs_seen, v_w], axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_w, keys) / jnp.sqrt(
            jnp.float32(d))
        seen = jnp.concatenate(
            [jnp.ones((window, ks_seen.shape[1]), bool), causal], axis=1)
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e9), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, values)

    out = []
    for w in range(length // window):
        at = slice(w * window, (w + 1) * window)
        earlier = slice(0, w * window // chunk)
        out.append(one_window(q[:, at], k[:, at], v[:, at], ks[:, earlier],
                              vs[:, earlier]))
    return jnp.concatenate(out, axis=1)


def attention(h, a, *, head_dim, theta, window, chunk):
    b, length, _ = h.shape
    q, k, v = ((h @ a[name]["kernel"]).reshape(b, length, -1, head_dim)
               for name in ("query", "key", "value"))
    ctx = eva_attention(rotary(q, theta), rotary(k, theta), v, a["phi"],
                        a["mu"], window=window, chunk=chunk)
    return ctx.reshape(b, length, -1) @ a["out"]["kernel"]


def block(p, x, *, eps, attn):
    x = x + attention(unit_offset_norm(x, p["ln_attn"]["scale"], eps),
                      p["attn"], **attn)
    return x + gated_mlp(unit_offset_norm(x, p["ln_mlp"]["scale"], eps),
                         p["mlp"])


def loss(params, batch, *, n_layers: int, head_dim: int, window: int,
         chunk: int, n_pred_heads: int, rope_theta: float, rms_eps: float):
    tokens = batch["tokens"]
    length = tokens.shape[1] - 1
    x = params["embed"]["embedding"][tokens[:, :-1]]
    attn = dict(head_dim=head_dim, theta=rope_theta, window=window, chunk=chunk)
    for i in range(n_layers):
        x = jax.checkpoint(lambda p, x: block(p, x, eps=rms_eps, attn=attn))(
            params[f"block_{i}"], x)
    x = unit_offset_norm(x, params["ln_f"]["scale"], rms_eps)
    logits = x @ params["lm_head"]["kernel"]
    vocab = logits.shape[-1] // n_pred_heads
    heads = [mean_nll(logits[:, :length - j, vocab * j:vocab * (j + 1)],
                      tokens[:, 1 + j:])
             for j in range(n_pred_heads)]
    return sum(heads) / n_pred_heads
