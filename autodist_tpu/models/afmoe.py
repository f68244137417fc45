"""AFMoE (``model_type`` ``afmoe``: Arcee's Trinity family) — a decoder-only LM
that mixes sliding-window and full attention under grouped KV heads, gates
the attention output, and follows the leading dense layers with a sigmoid
top-k mixture of gated-SiLU experts beside a shared expert, of which this
layer may hold one chip's share.

The equations, from the published ``config.json`` and ``modeling_afmoe.py``
(what the config does not carry is marked *assumed*, and listed with its
source in ``benchmark/configs/trinity-mini.json``). ``T`` tokens, width
``d``, ``H`` query heads over ``H_kv`` KV heads of ``head_dim``::

    x0 = E[tokens] * sqrt(d)                                  (mup_enabled; assumed factor)
    per layer l, kind = layer_types[l] in {sliding_attention, full_attention}:
      h  = RMSNorm_in(x)
      q, k, v = h.Wq [d, H*hd], h.Wk [d, H_kv*hd], h.Wv;  g = h.Wg [d, H*hd]   (no bias; assumed gate)
      q, k = RMSNorm_q(q), RMSNorm_k(k)      per head, over head_dim            (assumed)
      sliding: q, k = rope(q), rope(k)       rotate-half, whole head;  full: no position signal
      s_ij = q_i.k_j / sqrt(hd)  for j <= i, and on a sliding layer i - j < window;
             query head n reads KV head n // (H / H_kv)
      a  = softmax_j(s).v;  a = a * sigmoid(g);  x = x + RMSNorm_post_attn(a.Wo)
      h  = RMSNorm_pre_mlp(x)
      l < n_dense_layers:  m = W_down(silu(W_gate h) * W_up h), width d_ff
      else:  s = sigmoid(h.Wr [d, E]) in float32
             chosen = top_k(s + b)           b = expert_bias [E], in the choice only, no gradient
             w = s[chosen] / (sum over chosen of s + 1e-20) * route_scale       (route_norm)
             m = shared(h) + sum over chosen e of w_e . W_down,e(silu(W_gate,e h) * W_up,e h)
      x = x + RMSNorm_post_mlp(m)
    logits = RMSNorm_f(x).W_head  (untied);  loss = mean next-token cross-entropy
    after each optimizer step, per expert layer, c_e = rows expert e received in the step:
      delta = load_balance_coeff * sign(mean(c) - c_e);  b += delta - mean(delta)

**One chip's share.** ``n_experts_routed`` is the router's width;
``experts_held`` of them, from ``first_expert_held`` on, have their banks
here. The router scores and chooses over all of them, this layer adds its own
experts' part and leaves the rest out (``models/moe.py`` ``routed_experts``:
held rows sorted first and computed ``rows_bound`` at a time, in as many
passes as a step's routing needs): what expert parallelism asks of a rank,
without the exchange. The first pass keeps the gathered rows and the three
products for its backward (the bound is twice the mean held rows, so it is
nearly always the only one, and is computed once a step); a pass past it
keeps nothing and is computed again for the backward, since their number is
known only at run time. The layer sows that number (``passes``) beside the
loads. The shared expert, attention, the router and the dense layer are what
every rank computes alike.

**The expert bias on the normal path.** ``expert_bias`` is a parameter leaf
(float32 ``[E]``, zeros). It enters the choice under ``stop_gradient``, and
the loss carries the term ``sum_e (b_e - stop_gradient(b_e)) .
stop_gradient(c_e - mean c) / T`` a layer: its value is exactly zero (the
loss is the cross-entropy and nothing else) and ``d loss / d b_e`` is the
layer's load error; :func:`make_optimizer` (``models/moe.py``
``balanced_optimizer``) gives those leaves sign-SGD at
``load_balance_coeff`` with the mean removed (``optax.multi_transform``; every
other leaf AdamW), which is the rule above, through ``AutoDist(...).function``
and ``training.train`` unchanged. Departure from the published rule: under
gradient accumulation the sign is of the load error summed over the
micro-batches' means. :func:`balance_expert_bias` runs the same rule alone,
without a weight update, to start a randomly initialised router from the
balanced loads a trained one has.

Parameters and the residual stream are float32; the sublayers compute in
``dtype``; the router reads the float32 normalised input at ``HIGHEST``
precision, as OLMoE's does.
"""

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from autodist_tpu.models.common import RMSNorm, rope
from autodist_tpu.models.decoder import Decoder, init_params, make_loss_fn  # noqa: F401
from autodist_tpu.models.moe import (  # noqa: F401 — the mixture's, under this family's names
    GatedMLP, RoutedShare, _dense, balance_expert_bias,
    balanced_optimizer as make_optimizer, check_share, expert_loads, sown_loads)
from autodist_tpu.models.transformer_lm import (  # noqa: F401 — synthetic_batch re-exported
    dot_product_attention, synthetic_batch)

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """Defaults are Trinity-Mini's published sizes, every expert held."""
    vocab_size: int = 200192
    d_model: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 8
    n_dense_layers: int = 2
    d_ff: int = 6144                  # the dense layers' width
    d_expert: int = 1024              # one expert's width; the shared expert's too
    n_experts_routed: int = 128       # the router's width
    experts_held: int = 128           # experts whose banks live here ...
    first_expert_held: int = 0        # ... from this one on
    top_k: int = 8
    n_shared_experts: int = 1
    window: int = 2048
    rows_bound: Optional[int] = None  # held rows a pass computes; None: tokens x top_k
    route_norm: bool = True
    route_scale: float = 2.826
    load_balance_coeff: float = 1e-3
    mup_enabled: bool = True
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    max_len: int = 131072
    dtype: Any = jnp.bfloat16         # what the sublayers compute in
    attention_impl: str = "dot"       # "dot" | "flash"
    fused_head: bool = False          # pallas head + loss (ops/fused_xent)

    def __post_init__(self):
        if self.attention_impl not in ("dot", "flash"):
            raise ValueError(f"Unknown attention_impl {self.attention_impl!r}; "
                             f"valid: 'dot', 'flash'")
        unknown = set(self.layer_types) - {SLIDING, FULL}
        if unknown or not self.layer_types:
            raise ValueError(f"layer_types must be of {SLIDING!r} and {FULL!r}; "
                             f"got {sorted(unknown)}")
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError("n_heads must divide over n_kv_heads, head_dim even")
        check_share(self)

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)


def band_mask(length: int, window: Optional[int], dtype) -> jax.Array:
    """Additive ``[L, L]`` mask: key ``j`` is visible to query ``i`` where
    ``j <= i`` and, under a window, ``i - j < window``."""
    i = jnp.arange(length)[:, None]
    j = jnp.arange(length)[None, :]
    visible = j <= i
    if window is not None:
        visible &= i - j < window
    return jnp.where(visible, jnp.zeros((), dtype), jnp.full((), -1e9, dtype))


class GatedAttention(nn.Module):
    """Causal attention of one layer kind: RMSNorm on q and k per head, RoPE
    on a sliding layer only, ``H`` query heads over ``H_kv`` KV heads, the
    output gated by ``sigmoid(h.Wg)`` before the output projection."""
    config: AfmoeConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, length, _ = x.shape
        sliding = self.kind == SLIDING
        heads = lambda t, n: t.reshape(b, length, n, cfg.head_dim)  # noqa: E731
        wide, narrow = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        q = heads(_dense(wide, cfg.dtype, "query")(x), cfg.n_heads)
        k = heads(_dense(narrow, cfg.dtype, "key")(x), cfg.n_kv_heads)
        v = _dense(narrow, cfg.dtype, "value")(x)
        gate = _dense(wide, cfg.dtype, "gate")(x)
        q = RMSNorm(cfg.rms_eps, cfg.dtype, name="q_norm")(q)
        k = RMSNorm(cfg.rms_eps, cfg.dtype, name="k_norm")(k)
        if sliding:
            positions = jnp.arange(length)
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        window = cfg.window if sliding else None
        if cfg.attention_impl == "flash" and not self.is_initializing():
            from autodist_tpu.ops.flash_attention import flash_attention
            # v goes from its projection into the kernels and the result
            # from them into the gate as rows, read and written where they
            # lie; q and k have been normed a head (and turned) since theirs
            ctx = flash_attention(q, k, v, causal=True, window=window,
                                  heads=(cfg.n_heads, cfg.n_kv_heads))
        else:
            group = cfg.n_heads // cfg.n_kv_heads
            ctx = dot_product_attention(
                q, jnp.repeat(k, group, axis=2),
                jnp.repeat(heads(v, cfg.n_kv_heads), group, axis=2),
                band_mask(length, window, cfg.dtype), cfg.dtype)
        with jax.named_scope("attn.gate"):
            ctx = ctx.reshape(b, length, wide) * nn.sigmoid(gate)
        return _dense(cfg.d_model, cfg.dtype, "out")(ctx)


class AfmoeBlock(nn.Module):
    config: AfmoeConfig
    kind: str
    dense: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        norm = lambda name, dtype: RMSNorm(cfg.rms_eps, dtype, name=name)  # noqa: E731
        a = GatedAttention(cfg, self.kind, name="attn")(
            norm("ln_in", cfg.dtype)(x))
        x = x + norm("ln_post_attn", jnp.float32)(a)
        h = norm("ln_pre_mlp", jnp.float32)(x)
        if self.dense:
            m = GatedMLP(cfg.d_ff, cfg.dtype, name="mlp")(h.astype(cfg.dtype))
            bias_term = jnp.zeros((), jnp.float32)
        else:
            m, bias_term = RoutedShare(
                cfg, cfg.d_expert * cfg.n_shared_experts, name="moe")(h)
        return x + norm("ln_post_mlp", jnp.float32)(m), bias_term


class Afmoe(Decoder):
    """``tokens [B, L] -> (logits or hidden, bias term)``; the bias term is
    the sum over the expert layers of the zero-valued term whose gradient is
    the load error (module docstring)."""
    config: AfmoeConfig
    block = AfmoeBlock

    def layers(self):
        return [(kind, i < self.config.n_dense_layers)
                for i, kind in enumerate(self.config.layer_types)]
