"""OLMoE — a decoder-only LM whose every block is attention with QK-norm and
RoPE followed by a dropless top-k mixture of gated-SiLU experts.

The equations are those of the published ``modeling_olmoe.py`` (Muennighoff et
al. 2024, arXiv:2409.02060), per layer::

    h = RMSNorm(x);  q, k, v = h.Wq, h.Wk, h.Wv              (no bias)
    q, k = RMSNorm_q(q), RMSNorm_k(k)     over the whole projection, before
                                          the split into heads
    q, k = rope(q), rope(k)               rotate-half, the whole head dim
    x += softmax(q.k^T / sqrt(head_dim), causal).v . Wo
    h = RMSNorm(x);  p = softmax(h.Wg)    float32, over all experts
    x += sum over the top_k experts e of p_e . W_down,e(silu(W_gate,e h) * W_up,e h)

with the ``top_k`` probabilities as they are (``norm_topk_prob`` false), no
shared expert and no dropped token; after the last layer ``RMSNorm`` and an
untied head. No position table, no bias anywhere.

The layers live where the next models find them: ``RMSNorm`` and ``rope`` in
``models/common.py``, the routing and the routed FFN in ``models/moe.py``, the
grouped products in ``ops/grouped_matmul.py``, the stack, the loss and the
init in ``models/decoder.py``. Parameters and the residual
stream ``x`` are float32; the sublayers compute in ``dtype`` (their norms read
the float32 stream) and the router reads the float32 normalised ``h``, so
that fewer top-8 choices hang on a rounding (PERF.md §6, PR 25).
"""

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from autodist_tpu.models.common import RMSNorm, rope
from autodist_tpu.models.decoder import Decoder, init_params, make_loss_fn  # noqa: F401
from autodist_tpu.models.moe import RoutedFFN, _dense
from autodist_tpu.models.transformer_lm import (  # noqa: F401 — synthetic_batch re-exported
    causal_mask, dot_product_attention, synthetic_batch)


@dataclasses.dataclass(frozen=True)
class OlmoeConfig:
    """Defaults are OLMoE-1B-7B's published sizes."""
    vocab_size: int = 50304
    d_model: int = 2048
    n_heads: int = 16
    n_layers: int = 16
    d_expert: int = 1024          # one expert's width (``intermediate_size``)
    n_experts: int = 64
    top_k: int = 8
    max_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16     # what the sublayers compute in; the residual
                                  # stream and the parameters stay float32
    attention_impl: str = "dot"   # "dot" | "flash"
    fused_head: bool = False      # pallas head + loss (ops/fused_xent)
    # The paper's auxiliary losses; config.json carries neither weight.
    load_balance_weight: float = 0.01
    router_z_weight: float = 0.001

    def __post_init__(self):
        if self.attention_impl not in ("dot", "flash"):
            raise ValueError(f"Unknown attention_impl {self.attention_impl!r}; "
                             f"valid: 'dot', 'flash'")
        if self.d_model % self.n_heads or (self.d_model // self.n_heads) % 2:
            raise ValueError("d_model must divide into heads of even size")
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError("top_k must be in [1, n_experts]")


class QKNormAttention(nn.Module):
    """Causal multi-head attention, RMSNorm on the whole q and k projections,
    then RoPE on each head."""
    config: OlmoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, length, _ = x.shape
        head_dim = cfg.d_model // cfg.n_heads
        dense = lambda name: _dense(cfg.d_model, cfg.dtype, name)  # noqa: E731
        q = RMSNorm(cfg.rms_eps, cfg.dtype, name="q_norm")(dense("query")(x))
        k = RMSNorm(cfg.rms_eps, cfg.dtype, name="k_norm")(dense("key")(x))
        v = dense("value")(x)
        heads = lambda t: t.reshape(b, length, cfg.n_heads, head_dim)  # noqa: E731
        positions = jnp.arange(length)
        q = rope(heads(q), positions, cfg.rope_theta)
        k = rope(heads(k), positions, cfg.rope_theta)
        if cfg.attention_impl == "flash" and not self.is_initializing():
            from autodist_tpu.ops.flash_attention import flash_attention
            ctx = flash_attention(q, k, heads(v), causal=True)
        else:
            ctx = dot_product_attention(q, k, heads(v),
                                        causal_mask(length, cfg.dtype),
                                        cfg.dtype)
        return dense("out")(ctx.reshape(b, length, cfg.d_model))


class OlmoeBlock(nn.Module):
    config: OlmoeConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="ln_attn")(x)
        x = x + QKNormAttention(cfg, name="attn")(h).astype(jnp.float32)
        h = RMSNorm(cfg.rms_eps, jnp.float32, name="ln_moe")(x)
        y, aux = RoutedFFN(cfg.n_experts, cfg.top_k, cfg.d_expert, cfg.dtype,
                           name="moe")(h)
        return x + y, aux


class Olmoe(Decoder):
    """``tokens [B, L] -> (logits or hidden, aux)``; ``aux`` holds the two
    router losses, each the mean over the layers."""
    config: OlmoeConfig
    block = OlmoeBlock

    def __call__(self, tokens, return_hidden: bool = False):
        out, aux = super().__call__(tokens, return_hidden)
        return out, jax.tree_util.tree_map(
            lambda a: a / self.config.n_layers, aux)

    def loss(self, nll, aux):
        """+ ``load_balance_weight`` x the load-balancing loss +
        ``router_z_weight`` x the router z-loss."""
        cfg = self.config
        return (nll + cfg.load_balance_weight * aux["load_balance"]
                + cfg.router_z_weight * aux["router_z"])
