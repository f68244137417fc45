"""LFM2-MoE (``model_type`` ``lfm2_moe``: LiquidAI's LFM2-8B-A1B / 24B-A2B) — a
decoder-only LM whose token mixer is a gated short convolution in three
layers of four and grouped-query attention in the fourth, with a sigmoid
top-k mixture of gated-SiLU experts after the leading dense layers, of which
this layer may hold one chip's share.

The equations, from the published ``config.json`` and ``modeling_lfm2_moe.py``
(what the config does not carry is marked *assumed*, and listed with its
source in ``benchmark/configs/lfm2-24b-a2b.json``). ``T`` tokens, width
``d``, ``H`` query heads over ``H_kv`` KV heads of ``head_dim``, ``K`` taps::

    x0 = E[tokens]                                      (no scale; assumed: the head is tied to E)
    per layer l, kind = layer_types[l] in {conv, full_attention}:
      h = RMSNorm_operator(x)
      conv:  [B | C | u] = h.W_in [d, 3d]               (no bias; assumed: the thirds in this order)
             v   = B * u
             c_t = sum_{j=0..K-1} w[:, j] * v_{t-(K-1)+j}    depthwise, w [d, K], v_s = 0 for s < 0
                                                        (conv_L_cache K: Conv1d(d, d, K, groups=d,
                                                        padding=K-1), the first L outputs)
             a   = (C * c).W_out [d, d]                 (no activation anywhere in the operator)
      full_attention:
             q, k, v = h.Wq [d, H*hd], h.Wk [d, H_kv*hd], h.Wv      (no bias)
             q, k = RMSNorm_q(q), RMSNorm_k(k)          per head over head_dim, one weight [hd] each (assumed)
             q, k = rope(q), rope(k)                    rotate-half over the whole head, theta 1e6
             s_ij = q_i.k_j / sqrt(hd) for j <= i;  query head n reads KV head n // (H / H_kv)
             a    = softmax_j(s).v . Wo [H*hd, d]
      x = x + a
      h = RMSNorm_ffn(x)
      l < n_dense_layers:  m = W_down(silu(W_gate h) * W_up h), width d_ff     (w2, w1, w3)
      else:  s = sigmoid(h.Wr [d, E]) in float32
             chosen = top_k(s + b)        b = expert_bias [E], in the choice only, no gradient
             w = s[chosen] / (sum over chosen of s + 1e-6) * route_scale       (norm_topk_prob)
             m = sum over chosen e of w_e . W_down,e(silu(W_gate,e h) * W_up,e h);  no shared expert
      x = x + m
    logits = RMSNorm_f(x).E^T  (the model's embedding_norm; tied);  loss = mean next-token cross-entropy
    after each optimizer step, per expert layer, c_e = rows expert e received in the step:
      delta = load_balance_coeff * sign(mean(c) - c_e);  b += delta - mean(delta)        (assumed)

**One chip's share**, **the expert bias on the normal path** and its start
from the balancing rule alone are ``models/afmoe.py``'s, word for word, and
the code is the same code: ``models/moe.py`` ``RoutedShare`` (no shared
expert), ``balanced_optimizer``, ``balance_expert_bias``. The conv and
attention operators, the router and the dense layer are what every rank
computes alike. The stack, the loss and the init are ``models/decoder.py``'s.

The gated convolution between the two projections is one operator,
``ops/short_conv.py`` ``gated_short_conv``, plain (``conv_impl="xla"``) or as
two Pallas kernels (``"pallas"``); init runs the plain one.

Parameters and the residual stream are float32; the sublayers compute in
``dtype``; the router reads the float32 normalised input at ``HIGHEST``
precision, as OLMoE's and AFMoE's do.
"""

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from autodist_tpu.models.common import RMSNorm, rope
from autodist_tpu.models.decoder import Decoder, init_params, make_loss_fn  # noqa: F401
from autodist_tpu.models.moe import (  # noqa: F401 — the mixture's, under this family's names
    GatedMLP, RoutedShare, _dense, _INIT, balance_expert_bias,
    balanced_optimizer as make_optimizer, check_share, expert_loads, sown_loads)
from autodist_tpu.models.transformer_lm import (  # noqa: F401 — synthetic_batch re-exported
    causal_mask, dot_product_attention, synthetic_batch)
from autodist_tpu.ops.short_conv import IMPLS as CONV_IMPLS, gated_short_conv

CONV, FULL = "conv", "full_attention"


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """Defaults are LFM2-24B-A2B's published sizes, every expert held."""
    vocab_size: int = 65536
    d_model: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    layer_types: Tuple[str, ...] = (CONV, CONV, FULL, CONV) * 10
    n_dense_layers: int = 2
    d_ff: int = 11776                 # the dense layers' width
    d_expert: int = 1536              # one expert's width
    n_experts_routed: int = 64        # the router's width
    experts_held: int = 64            # experts whose banks live here ...
    first_expert_held: int = 0        # ... from this one on
    top_k: int = 4
    conv_kernel: int = 3              # conv_L_cache: taps of the short convolution
    rows_bound: Optional[int] = None  # held rows a pass computes; None: tokens x top_k
    route_norm: bool = True           # norm_topk_prob
    route_scale: float = 1.0          # routed_scaling_factor
    route_eps: float = 1e-6           # in the normaliser of the chosen scores
    load_balance_coeff: float = 1e-3
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-5
    max_len: int = 128000
    dtype: Any = jnp.bfloat16         # what the sublayers compute in
    attention_impl: str = "dot"       # "dot" | "flash"
    conv_impl: str = "xla"            # "xla" | "pallas" (ops/short_conv)
    fused_head: bool = False          # pallas head + loss (ops/fused_xent)

    def __post_init__(self):
        if self.attention_impl not in ("dot", "flash"):
            raise ValueError(f"Unknown attention_impl {self.attention_impl!r}; "
                             f"valid: 'dot', 'flash'")
        if self.conv_impl not in CONV_IMPLS:
            raise ValueError(f"Unknown conv_impl {self.conv_impl!r}; "
                             f"valid: {CONV_IMPLS}")
        unknown = set(self.layer_types) - {CONV, FULL}
        if unknown or not self.layer_types:
            raise ValueError(f"layer_types must be of {CONV!r} and {FULL!r}; "
                             f"got {sorted(unknown)}")
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError("n_heads must divide over n_kv_heads, head_dim even")
        check_share(self)

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)


class ShortConv(nn.Module):
    """The conv operator: input projection to ``[B | C | u]``, the gated
    causal depthwise convolution, output projection. No bias, no activation."""
    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        taps = self.param("conv", _INIT, (cfg.d_model, cfg.conv_kernel),
                          jnp.float32)
        with jax.named_scope("conv.in_proj"):
            bcu = _dense(3 * cfg.d_model, cfg.dtype, "in_proj")(x)
        with jax.named_scope("conv.gate_conv"):
            impl = "xla" if self.is_initializing() else cfg.conv_impl
            y = gated_short_conv(bcu, taps, impl)
        with jax.named_scope("conv.out_proj"):
            return _dense(cfg.d_model, cfg.dtype, "out_proj")(y)


class GroupedAttention(nn.Module):
    """Causal attention: RMSNorm on q and k per head, RoPE on every layer,
    ``H`` query heads over ``H_kv`` KV heads, no gate."""
    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, length, _ = x.shape
        heads = lambda t, n: t.reshape(b, length, n, cfg.head_dim)  # noqa: E731
        wide, narrow = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        q = heads(_dense(wide, cfg.dtype, "query")(x), cfg.n_heads)
        k = heads(_dense(narrow, cfg.dtype, "key")(x), cfg.n_kv_heads)
        v = heads(_dense(narrow, cfg.dtype, "value")(x), cfg.n_kv_heads)
        q = RMSNorm(cfg.rms_eps, cfg.dtype, name="q_norm")(q)
        k = RMSNorm(cfg.rms_eps, cfg.dtype, name="k_norm")(k)
        positions = jnp.arange(length)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        if cfg.attention_impl == "flash" and not self.is_initializing():
            from autodist_tpu.ops.flash_attention import flash_attention
            ctx = flash_attention(q, k, v, causal=True)
        else:
            group = cfg.n_heads // cfg.n_kv_heads
            ctx = dot_product_attention(
                q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2),
                causal_mask(length, cfg.dtype), cfg.dtype)
        return _dense(cfg.d_model, cfg.dtype, "out")(ctx.reshape(b, length, wide))


class Lfm2MoeBlock(nn.Module):
    config: Lfm2MoeConfig
    kind: str
    dense: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="operator_norm")(x)
        if self.kind == CONV:
            a = ShortConv(cfg, name="conv")(h)
        else:
            a = GroupedAttention(cfg, name="attn")(h)
        x = x + a
        h = RMSNorm(cfg.rms_eps, jnp.float32, name="ffn_norm")(x)
        if self.dense:
            m = GatedMLP(cfg.d_ff, cfg.dtype, name="mlp")(h.astype(cfg.dtype))
            bias_term = jnp.zeros((), jnp.float32)
        else:
            m, bias_term = RoutedShare(cfg, name="moe")(h)   # no shared expert
        return x + m, bias_term


class Lfm2Moe(Decoder):
    """``tokens [B, L] -> (logits or hidden, the expert layers' bias terms
    summed: ``models/afmoe.py``'s docstring)``. The head is the embedding table."""
    config: Lfm2MoeConfig
    block = Lfm2MoeBlock
    final_norm = "embedding_norm"
    tied = True

    def layers(self):
        return [(kind, i < self.config.n_dense_layers)
                for i, kind in enumerate(self.config.layer_types)]
