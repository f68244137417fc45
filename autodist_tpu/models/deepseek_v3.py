"""DeepSeek-V3 family (``model_type`` ``deepseek_v3``: Kakao's Kanana-2-30B-A3B
among others) — a decoder-only LM whose attention is *latent* (MLA): keys and
values come through a normed low-rank latent, one rotary key head is shared
by all query heads, and a key is wider than a value. The leading dense layers
are followed by a sigmoid top-k mixture of gated-SiLU experts beside shared
experts, of which this layer may hold one chip's share.

The equations, from the published ``config.json`` (``q_lora_rank`` null: the
query has no low-rank path; what the config does not carry is marked
*assumed*, and listed with its source in
``benchmark/configs/kanana-2-30b-a3b.json``). ``T`` tokens, width ``d``, ``H``
heads, a key ``d_n + d_r`` wide (``qk_nope_head_dim`` + ``qk_rope_head_dim``),
a value ``d_v``, the latent ``r`` (``kv_lora_rank``)::

    x0 = E[tokens]                                          (no scale)
    per layer l:
      h  = RMSNorm_attn(x)
      q  = h.W_q [d, H (d_n + d_r)]                a head: [q_nope d_n | q_rope d_r]     (no bias)
      [c | k_rope] = h.W_kva [d, r + d_r];  c = RMSNorm_kv(c)   (its own weight, the layer's eps)
      [k_nope d_n | v d_v] a head = c.W_kvb [r, H (d_n + d_v)]
      q_rope, k_rope = rope(q_rope), rope(k_rope)  over the pairs (2i, 2i+1), theta, no scaling
                       (rope_interleave; the columns stay in their order: assumed, immaterial,
                       q and k share it)
      k  = [k_nope | k_rope], k_rope the same for every head
      s_ij = q_i.k_j / sqrt(d_n + d_r)  for j <= i;   a = softmax_j(s).v;   x = x + a.W_o [H d_v, d]
      h  = RMSNorm_mlp(x)
      l < n_dense_layers:  m = W_down(silu(W_gate h) * W_up h), width d_ff
      else:  s = sigmoid(h.Wr [d, E]) in float32
             chosen = top_k(s + b)          b = expert_bias [E], in the choice only, no gradient
                                            (noaux_tc; n_group 1, topk_group 1: no group-limited choice)
             w = s[chosen] / (sum over chosen of s + 1e-20) * routed_scaling_factor    (norm_topk_prob)
             m = shared(h) + sum over chosen e of w_e . W_down,e(silu(W_gate,e h) * W_up,e h)
             shared: the n_shared_experts as one gated-SiLU MLP n_shared x d_expert wide
      x = x + m
    logits = RMSNorm_f(x).W_head  (untied);  loss = mean next-token cross-entropy
    after each optimizer step, per expert layer, c_e = rows expert e received in the step:
      delta = load_balance_coeff * sign(mean(c) - c_e);  b += delta - mean(delta)        (assumed)

No multi-token-prediction module. **One chip's share**, **the expert bias on
the normal path** and its start from the balancing rule alone are
``models/afmoe.py``'s, word for word, and the code is the same code:
``models/moe.py`` ``RoutedShare``, ``balanced_optimizer``,
``balance_expert_bias``; the stack, the loss and the init ``models/decoder.py``'s.

**The attention core** is one call: ``ops/flash_attention.py``
``flash_attention`` with a value width of its own and the rotary key as
``k_shared`` (read once a layer through the index maps, never repeated in
memory; its gradient the sum over the heads), or under ``attention_impl="dot"``
the quadratic form on an assembled key. ``kv_up``'s output goes into that
call whole, as the rows the product wrote (a head's 128 key columns then its
128 values: the kernels read both where they lie and hand d(kv) back as one
array), and the result comes out as rows for the output projection; q is
turned in between and goes in ``[B, L, H, 192]`` (PR 41).

Under ``remat`` every layer is a ``jax.checkpoint`` whose policy keeps the
values named in :data:`KEPT` and recomputes everything else from the residual
stream (``models/nemotron_h.py`` has the mechanism's story). Latent
attention's own lever is in the list: **the normed latent and the rotary
key, ``r + d_r`` numbers a token, stand in for K and V, ``H (d_n + d_r) + H
d_v``**, and ``W_kvb``'s product is made again (8.4 MFLOP a token beside the
core's 168 at 16,384 positions). Beside them the list holds q, what flash's
forward rule hands its backward (``o``, the log-sum-exp: the core's forward
is the dearest thing a layer could make again), the router's logits (six
bfloat16 passes), the MLPs' ``gate`` and ``up`` products (the dense layer's
and the shared experts') and what pass 0 of the routed share makes for its
transpose. Every product out of the 2,048-wide stream costs the same to make
again for the bytes it takes to keep (2,048 operations a byte), so what ends
the list is memory: the benchmark's cell compiles to 14.39 GiB of a 15.75 GiB
chip with the list and to 12.85 without its last three names, which cost 12
ms of a 1,003 ms step to make again (``benchmark/rehearse.py``; PERF.md
section 6, "PR 37"). Gauge ``mla.kept_bytes_per_token`` says what the
attention's named values take.

Parameters and the residual stream are float32; the sublayers compute in
``dtype``; the router reads the float32 normalised input at ``HIGHEST``
precision, as OLMoE's and AFMoE's do.
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from autodist_tpu import telemetry
from autodist_tpu.models.common import RMSNorm, head_columns, rope_pairs
from autodist_tpu.models.decoder import Decoder, init_params, make_loss_fn  # noqa: F401
from autodist_tpu.models.moe import (  # noqa: F401 — the mixture's, under this family's names
    KEPT_GATE, KEPT_PASS, KEPT_ROUTER_LOGITS, KEPT_UP, GatedMLP, RoutedShare,
    _dense, balance_expert_bias, balanced_optimizer as make_optimizer,
    check_share, expert_loads, sown_loads)
from autodist_tpu.models.transformer_lm import (  # noqa: F401 — synthetic_batch re-exported
    causal_mask, dot_product_attention, synthetic_batch)
from autodist_tpu.ops.flash_attention import KEPT_NAME as KEPT_FLASH

KEPT_QUERY = "mla_query"              # q, before its rotary columns turn
KEPT_LATENT = "mla_latent"            # c, normed
KEPT_ROPE_KEY = "mla_rope_key"        # the one rotary key head, turned
# What a checkpointed layer keeps for its backward (module docstring; PERF.md
# section 6, "PR 37", has the lists that were tried)
KEPT = (KEPT_QUERY, KEPT_LATENT, KEPT_ROPE_KEY, KEPT_FLASH, KEPT_ROUTER_LOGITS,
        KEPT_GATE, KEPT_UP, KEPT_PASS)


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    """Defaults are Kanana-2-30B-A3B's published sizes, every expert held."""
    vocab_size: int = 128256
    d_model: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    n_dense_layers: int = 1           # first_k_dense_replace
    d_ff: int = 6144                  # the dense layers' width
    d_expert: int = 768               # one expert's width
    n_experts_routed: int = 128       # the router's width
    experts_held: int = 128           # experts whose banks live here ...
    first_expert_held: int = 0        # ... from this one on
    top_k: int = 6
    n_shared_experts: int = 2
    rows_bound: Optional[int] = None  # held rows a pass computes; None: tokens x top_k
    route_norm: bool = True
    route_scale: float = 2.448
    route_eps: float = 1e-20
    load_balance_coeff: float = 1e-3
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    max_len: int = 32768
    dtype: Any = jnp.bfloat16         # what the sublayers compute in
    attention_impl: str = "dot"       # "dot" | "flash"
    fused_head: bool = False          # pallas head + loss (ops/fused_xent)
    remat: bool = False               # jax.checkpoint around every layer, keeping KEPT

    def __post_init__(self):
        if self.attention_impl not in ("dot", "flash"):
            raise ValueError(f"Unknown attention_impl {self.attention_impl!r}; "
                             f"valid: 'dot', 'flash'")
        if self.qk_rope_head_dim % 2 or self.qk_nope_head_dim % 2:
            raise ValueError("the key's two parts must be even")
        check_share(self)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


class LatentAttention(nn.Module):
    """Causal multi-head latent attention: the keys' position-free part and
    the values through a normed ``kv_lora_rank``-wide latent, one rotary key
    head for all query heads, no bias. ``config`` is a family's: the widths,
    ``n_heads``, ``rope_theta``, ``rms_eps``, ``dtype``, ``attention_impl``,
    ``remat`` (``models/bailing_hybrid.py`` hands its own).

    ``heads_held`` (None: the layer's ``n_heads``): one chip's share of the
    heads. ``query``, ``kv_up`` and ``out`` are the held heads' columns and
    rows; the latent, its norm and the rotary key are every chip's alike; what
    the other heads would add to the output projection's sum is left out
    (gauge ``attention.heads_held``). ``head_gate``: each head's output is
    multiplied by ``sigmoid(h.W_gate)`` of its own, ``W_gate [d, heads]``,
    before the output projection (a head-wise output gate)."""
    config: Any
    heads_held: Optional[int] = None
    head_gate: bool = False

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        b, length, _ = h.shape
        heads, d_n, d_r, d_v = (self.heads_held or cfg.n_heads,
                                cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                                cfg.v_head_dim)
        if self.heads_held is not None:
            telemetry.gauge("attention.heads_held").set(heads)
        kept = []       # bytes of what the layer's checkpoint keeps of this

        def name(x, what):
            kept.append(x.size * x.dtype.itemsize)
            return checkpoint_name(x, what)

        with jax.named_scope("mla.q_proj"):
            q = name(_dense(heads * cfg.qk_head_dim, cfg.dtype, "query")(h),
                     KEPT_QUERY).reshape(b, length, heads, cfg.qk_head_dim)
        with jax.named_scope("mla.kv_down"):
            down = _dense(cfg.kv_lora_rank + d_r, cfg.dtype, "kv_down")(h)
            c = name(RMSNorm(cfg.rms_eps, cfg.dtype, name="kv_norm")(
                down[..., :cfg.kv_lora_rank]), KEPT_LATENT)
        with jax.named_scope("mla.kv_up"):
            # a head's position-free key columns, then its values
            kv = _dense(heads * (d_n + d_v), cfg.dtype, "kv_up")(c)
        with jax.named_scope("mla.rope"):
            positions = jnp.arange(length)
            q = rope_pairs(q, positions, cfg.rope_theta, d_r)
            k_rope = name(rope_pairs(down[..., None, cfg.kv_lora_rank:],
                                     positions, cfg.rope_theta), KEPT_ROPE_KEY)
        if cfg.attention_impl == "flash" and not self.is_initializing():
            from autodist_tpu.ops.flash_attention import flash_attention
            # kv as the product wrote it, rows of heads * 256 columns: the
            # kernels find a head's keys and values where they lie and hand
            # d(kv), and the result, back as such rows. q has been turned
            # since its projection and goes in [B, L, H, 192].
            ctx = flash_attention(q, kv, None, causal=True, heads=(heads, heads),
                                  k_shared=k_rope[:, :, 0, :])
            # KEPT_FLASH: o and one float32 lse a head
            kept.append(ctx.size * ctx.dtype.itemsize + b * length * heads * 4)
        else:
            kv = kv.reshape(b, length, heads, d_n + d_v)
            k = jnp.concatenate([kv[..., :d_n], jnp.broadcast_to(
                k_rope, (b, length, heads, d_r))], axis=-1)
            ctx = dot_product_attention(q, k, kv[..., d_n:],
                                        causal_mask(length, cfg.dtype), cfg.dtype)
        telemetry.gauge("mla.kept_bytes_per_token").set(
            sum(kept) // (b * length) if cfg.remat else 0)
        if self.head_gate:
            with jax.named_scope("mla.head_gate"):
                # a head's gate over its d_v columns as a product with 0 / 1
                # columns: the rows stay the rows flash wrote
                gate = jax.nn.sigmoid(_dense(heads, cfg.dtype, "gate")(h))
                ctx = ctx.reshape(b, length, heads * d_v) * (
                    gate @ head_columns(heads, d_v, cfg.dtype))
        with jax.named_scope("mla.out_proj"):
            return _dense(cfg.d_model, cfg.dtype, "out")(
                ctx.reshape(b, length, heads * d_v))


class DeepseekV3Block(nn.Module):
    """``x + Attn(RMSNorm(x))``, then ``+ FFN(RMSNorm(.))``; ``(x, the
    layer's bias term)``."""
    config: DeepseekV3Config
    dense: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        x = x + LatentAttention(cfg, name="attn")(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="ln_attn")(x))
        h = RMSNorm(cfg.rms_eps, jnp.float32, name="ln_mlp")(x)
        if self.dense:
            m = GatedMLP(cfg.d_ff, cfg.dtype, name="mlp")(h.astype(cfg.dtype))
            bias_term = jnp.zeros((), jnp.float32)
        else:
            m, bias_term = RoutedShare(
                cfg, cfg.d_expert * cfg.n_shared_experts, name="moe")(h)
        return x + m, bias_term


class DeepseekV3(Decoder):
    """``tokens [B, L] -> (logits or hidden, the expert layers' bias terms
    summed: ``models/afmoe.py``'s docstring)``."""
    config: DeepseekV3Config
    block = DeepseekV3Block
    kept = KEPT

    def layers(self):
        return [(i < self.config.n_dense_layers,)
                for i in range(self.config.n_layers)]
