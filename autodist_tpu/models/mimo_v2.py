"""MiMo-V2 family (``model_type`` ``mimo_v2``: Xiaomi's MiMo-V2-Flash and
MiMo-V2.5) — a decoder-only LM that mixes window-128 attention with a learned
sink and full attention under OTHER head counts, keys wider than values with
a rotary turn on a third of a head, and follows one leading dense layer with
a sigmoid top-k mixture of gated-SiLU experts without a shared one, of which
this layer may hold one chip's share.

The equations, from the published ``config.json`` (what the config does not
carry is marked *assumed*, and listed with its reason in
``benchmark/configs/mimo-v2.5.json``). ``T`` tokens, width ``d``, ``H`` query
heads, keys ``hd`` wide and values ``vd`` (192 / 128), ``kind_l =
hybrid_layer_pattern[l]`` (0 full, 1 sliding)::

    x0 = E[tokens]                                             (no scale)
    per layer l:
      h  = RMSNorm_in(x)                                       eps layernorm_epsilon
      H_kv  = num_key_value_heads (full) | swa_num_key_value_heads (sliding)
      theta = rope_theta (full)          | swa_rope_theta (sliding)
      q = h.Wq [d, H hd];  k = h.Wk [d, H_kv hd];  v = (h.Wv [d, H_kv vd]) * attention_value_scale
                                                               (no bias, no norm on q or k: assumed)
      q, k: the first int(hd * partial_rotary_factor) columns of every head turned by
            rope(theta), rotate-half over those columns; the rest pass as they are
            (the rotary columns lead the head: assumed; a permutation applied to q and k
            alike changes no score)
      s_ij = q_i.k_j / sqrt(hd)  for j <= i, and on a sliding layer i - j < window
             (the window counts the query itself); head n reads KV head n // (H / H_kv)
      full:    p = softmax_j(s)                                (add_full_attention_sink_bias false)
      sliding: p_ij = exp(s_ij) / (exp(sink_n) + sum_j' exp(s_ij'))
               sink [H] float32, learned, zeros at init (assumed): a logit with no value
      x  = x + (p.v reshaped [T, H vd]).Wo [H vd, d]
      h  = RMSNorm_post(x)
      moe_layer_freq[l] = 0 (layer 0):  m = W_down(silu(W_gate h) * W_up h), width d_ff
      else:  sc = sigmoid(h.Wr [d, E]) in float32
             chosen = top_k(sc + b)      b = expert_bias [E] (e_score_correction_bias):
                                         in the choice only, no gradient (noaux_tc; n_group 1)
             w = sc[chosen] / (sum over chosen of sc + 1e-20)   (norm_topk_prob;
                                         routed_scaling_factor null: 1; no shared expert)
             m = sum over chosen e of w_e . W_down,e(silu(W_gate,e h) * W_up,e h), width d_expert
      x  = x + m
    logits = RMSNorm_f(x).W_head  (untied);  loss = mean next-token cross-entropy
    after each optimizer step, per expert layer, c_e = rows expert e received in the step:
      delta = load_balance_coeff * sign(mean(c) - c_e);  b += delta - mean(delta)      (assumed)

Not built: the multi-token-prediction layers and the vision and audio towers
(no size of theirs is in the config). ``attention_chunk_size`` and the fused
qkv layout are inference and storage layouts that change no number here.

**One chip's share** and **the expert bias on the normal path** are
``models/afmoe.py``'s, word for word, and the code is the same code:
``models/moe.py`` ``RoutedShare`` (without a shared expert), ``balanced_optimizer``,
``balance_expert_bias``; the stack, the loss and the init ``models/decoder.py``'s.
The bias term is a mean over the batch the loss sees: under data parallelism
(``strategy.FullySharded`` too) that is the global batch, so the sign rule
reads the load error of every chip's tokens together, as one chip with the
whole batch would (``tests/test_mimo_v2.py`` holds it on a four-device mesh).

**The attention core** is one call: ``ops/flash_attention.py``
``flash_attention`` with the layer kind's KV heads, its window and, on a
sliding layer, ``sink`` (the online softmax starts from ``(sink_n, 1, 0)``;
the kernels of such a call carry the device names ``flash_sink_*``, so a
trace tells a sliding layer's time from a full one's), or under
``attention_impl="dot"`` the quadratic form with the sink as one more column
of the logits. q and k are turned between projection and call, so they go in
``[B, L, heads, 192]`` (192 is not whole lane tiles: XLA lays them out a head
a row block); ``v`` goes in as its projection's rows and the result comes out
as rows for the output projection.

**Stored as shares.** Under ``strategy.FullySharded`` every large leaf is a
quarter a chip. XLA gathers the dense products' weights where they are used;
the expert banks go to Mosaic kernels through ``per_device``, whose body
gathers a stored leaf before it calls the share (``parallel/mesh.py``
``stored_shards``) — before the loop over the passes past the first, whose
trip count differs from chip to chip and so may hold no collective — and
``RoutedShare`` casts a stored bank to ``dtype`` in front of that gather, so
half the bytes move. The block holds the residual stream to the batch
sharding at its edges (``constrain_batch``).

Under ``remat`` every layer is a ``jax.checkpoint`` that keeps the values
named in :data:`KEPT` (``models/nemotron_h.py`` has the mechanism's story):
k and v as the core reads them (turned, scaled), what flash's forward hands
its backward, the router's logits and what pass 0 of the share makes for its
transpose. q (named, not kept: two thirds of the core's operands) and the
dense layer's ``gate`` / ``up`` products are made again; memory ends the list.

Parameters and the residual stream are float32; the sublayers compute in
``dtype``; the router reads the float32 normalised input at ``HIGHEST``
precision, as the other mixtures' do; the sinks stay float32 into the kernel.
"""

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from autodist_tpu import telemetry
from autodist_tpu.models.common import RMSNorm, rope
from autodist_tpu.models.decoder import Decoder, init_params, make_loss_fn  # noqa: F401
from autodist_tpu.models.moe import (  # noqa: F401 — the mixture's, under this family's names
    KEPT_PASS, KEPT_ROUTER_LOGITS, GatedMLP, RoutedShare,
    _dense, balance_expert_bias, balanced_optimizer as make_optimizer,
    check_share, expert_loads, sown_loads)
from autodist_tpu.models.transformer_lm import synthetic_batch  # noqa: F401 — re-exported
from autodist_tpu.ops.flash_attention import KEPT_NAME as KEPT_FLASH
from autodist_tpu.parallel.mesh import constrain_batch

FULL, SLIDING = 0, 1                  # hybrid_layer_pattern's two kinds
KEPT_QUERY = "mimo_query"             # q, turned: two thirds of what the core reads
KEPT_KV = "mimo_kv"                   # k turned and v scaled, at the KV heads
# What a checkpointed layer keeps for its backward. The list ends where the
# four-chip cell's ceiling does (``benchmark/rehearse.py mimo-sharded4-8k``,
# PERF.md section 6, "PR 46"; GiB a chip of the compiled step, + 2.07 of the
# caller's quarter, of 15.75): with q and the dense layer's gate / up
# (KEPT_QUERY, KEPT_GATE, KEPT_UP) as well 14.47, without gate / up 13.94,
# without flash's residuals too 13.17, and as it stands, q made again from
# the residual stream (one 4,096 x 12,288 product and its turn a layer) and
# everything the flash forward made kept, 12.44. q is two thirds of what a
# layer's core reads and the largest value a layer could keep (201 MB at
# 8,192 positions, seven layers).
KEPT = (KEPT_KV, KEPT_FLASH, KEPT_ROUTER_LOGITS, KEPT_PASS)


@dataclasses.dataclass(frozen=True)
class MimoV2Config:
    """Defaults are MiMo-V2.5's published sizes, every expert held."""
    vocab_size: int = 152576
    d_model: int = 4096
    n_heads: int = 64                 # query heads, both kinds of layer
    n_kv_heads: int = 4               # a full layer's KV heads ...
    swa_n_kv_heads: int = 8           # ... and a sliding layer's
    head_dim: int = 192               # keys and queries
    v_head_dim: int = 128
    layer_pattern: Tuple[int, ...] = (FULL,) + ((SLIDING,) * 4 + (FULL,)
                                                + ((SLIDING,) * 5 + (FULL,)) * 7)
    moe_layer_freq: Tuple[int, ...] = (0,) + (1,) * 47     # 0: a dense MLP
    d_ff: int = 16384                 # the dense layer's width
    d_expert: int = 2048              # one expert's width
    n_experts_routed: int = 256       # the router's width
    experts_held: int = 256           # experts whose banks live here ...
    first_expert_held: int = 0        # ... from this one on
    top_k: int = 8
    window: int = 128                 # keys a sliding layer's query sees, itself included
    rows_bound: Optional[int] = None  # held rows a pass computes; None: tokens x top_k
    route_norm: bool = True
    route_scale: float = 1.0          # routed_scaling_factor null
    route_eps: float = 1e-20
    load_balance_coeff: float = 1e-3
    partial_rotary_factor: float = 0.334
    rope_theta: float = 1e7           # a full layer's base ...
    swa_rope_theta: float = 1e4       # ... and a sliding layer's
    value_scale: float = 0.707        # attention_value_scale
    rms_eps: float = 1e-5             # layernorm_epsilon
    max_len: int = 1048576
    dtype: Any = jnp.bfloat16         # what the sublayers compute in
    attention_impl: str = "dot"       # "dot" | "flash"
    fused_head: bool = False          # pallas head + loss (ops/fused_xent)
    remat: bool = False               # jax.checkpoint around every layer, keeping KEPT

    def __post_init__(self):
        if self.attention_impl not in ("dot", "flash"):
            raise ValueError(f"Unknown attention_impl {self.attention_impl!r}; "
                             f"valid: 'dot', 'flash'")
        if set(self.layer_pattern) - {FULL, SLIDING} or not self.layer_pattern:
            raise ValueError(f"layer_pattern must be of {FULL} (full) and "
                             f"{SLIDING} (sliding)")
        if len(self.moe_layer_freq) != len(self.layer_pattern):
            raise ValueError("moe_layer_freq must name every layer of layer_pattern")
        if self.n_heads % self.n_kv_heads or self.n_heads % self.swa_n_kv_heads:
            raise ValueError("n_heads must divide over both kinds' KV heads")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"rotary_dim {self.rotary_dim} of head_dim "
                             f"{self.head_dim} must be even and inside the head")
        check_share(self)

    @property
    def n_layers(self) -> int:
        return len(self.layer_pattern)

    @property
    def rotary_dim(self) -> int:
        """Columns of a head the rotary embedding turns: ``int(head_dim *
        partial_rotary_factor)``, 64 of 192."""
        return int(self.head_dim * self.partial_rotary_factor)


def sink_dot_attention(q, k, v, window: Optional[int], sink, dtype):
    """The quadratic form of one layer's attention: ``q [B, L, H, hd]`` over
    ``k [B, L, H_kv, hd]`` and ``v [B, L, H_kv, vd]`` (a KV head repeated over
    its group), causal, under a window of ``window`` keys, with ``sink [H]``
    (None: none) as one more column of the float32 logits whose probability
    meets no value. Returns ``[B, L, H, vd]`` in ``dtype``."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    length = q.shape[1]
    i, j = jnp.arange(length)[:, None], jnp.arange(length)[None, :]
    visible = j <= i
    if window is not None:
        visible &= i - j < window
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(visible, scores / np.sqrt(q.shape[-1]), -1e30)
    if sink is not None:
        column = jnp.broadcast_to(sink.astype(jnp.float32)[None, :, None, None],
                                  scores.shape[:3] + (1,))
        scores = jnp.concatenate([scores, column], axis=-1)
    probs = jax.nn.softmax(scores, axis=-1)[..., :length].astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


class SinkAttention(nn.Module):
    """Causal attention of one layer kind: ``H`` query heads over the kind's
    KV heads, keys 192 over values 128, the first ``rotary_dim`` columns of q
    and k turned at the kind's base, the values scaled, and on a sliding
    layer a window and a learned sink a head."""
    config: MimoV2Config
    sliding: bool

    @nn.compact
    def __call__(self, h):
        cfg, sliding = self.config, self.sliding
        b, length, _ = h.shape
        heads, d_k, d_v = cfg.n_heads, cfg.head_dim, cfg.v_head_dim
        kv_heads = cfg.swa_n_kv_heads if sliding else cfg.n_kv_heads
        theta = cfg.swa_rope_theta if sliding else cfg.rope_theta
        window = cfg.window if sliding else None
        q = _dense(heads * d_k, cfg.dtype, "query")(h).reshape(
            b, length, heads, d_k)
        k = _dense(kv_heads * d_k, cfg.dtype, "key")(h).reshape(
            b, length, kv_heads, d_k)
        v = _dense(kv_heads * d_v, cfg.dtype, "value")(h)
        sink = self.param("sink", nn.initializers.zeros, (heads,),
                          jnp.float32) if sliding else None
        with jax.named_scope("attn.rope_partial"):
            positions = jnp.arange(length)
            q = checkpoint_name(rope(q, positions, theta, cfg.rotary_dim),
                                KEPT_QUERY)
            k = checkpoint_name(rope(k, positions, theta, cfg.rotary_dim), KEPT_KV)
        v = checkpoint_name(v * jnp.asarray(cfg.value_scale, cfg.dtype), KEPT_KV)
        if cfg.attention_impl == "flash" and not self.is_initializing():
            from autodist_tpu.ops.flash_attention import band_pairs, flash_attention
            if sliding:
                # the (query, key) pairs the band keeps and the pairs of the
                # tiles the forward's walk runs, a step: every sliding layer
                # and head of this batch (set, not added: a layer is traced
                # more than once)
                visible, computed = band_pairs(length, length, True, window,
                                               d=d_k, itemsize=q.dtype.itemsize)
                calls = b * heads * cfg.layer_pattern.count(SLIDING)
                telemetry.gauge("attn.band_pairs_visible").set(calls * visible)
                telemetry.gauge("attn.band_pairs_computed").set(calls * computed)
            # v goes from its projection into the kernels and the result from
            # them into the output projection as rows; q and k have been
            # turned since theirs
            ctx = flash_attention(q, k, v, causal=True, window=window,
                                  heads=(heads, kv_heads), sink=sink)
        else:
            ctx = sink_dot_attention(q, k, v.reshape(b, length, kv_heads, d_v),
                                     window, sink, cfg.dtype)
        return _dense(cfg.d_model, cfg.dtype, "out")(
            ctx.reshape(b, length, heads * d_v))


class MimoV2Block(nn.Module):
    """``x + Attn(RMSNorm(x))``, then ``+ FFN(RMSNorm(.))``: two norms a
    layer; ``(x, the layer's bias term)``."""
    config: MimoV2Config
    sliding: bool
    dense: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        x = constrain_batch(x)
        x = x + SinkAttention(cfg, self.sliding, name="attn")(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="ln_in")(x))
        h = RMSNorm(cfg.rms_eps, jnp.float32, name="ln_post")(x)
        if self.dense:
            m = GatedMLP(cfg.d_ff, cfg.dtype, name="mlp")(h.astype(cfg.dtype))
            bias_term = jnp.zeros((), jnp.float32)
        else:
            m, bias_term = RoutedShare(cfg, name="moe")(h)
        return constrain_batch(x + m), bias_term


class MimoV2(Decoder):
    """``tokens [B, L] -> (logits or hidden, the expert layers' bias terms
    summed: ``models/afmoe.py``'s docstring)``."""
    config: MimoV2Config
    block = MimoV2Block
    kept = KEPT

    def layers(self):
        cfg = self.config
        telemetry.gauge("attn.sink_layers").set(cfg.layer_pattern.count(SLIDING))
        return [(kind == SLIDING, not moe)
                for kind, moe in zip(cfg.layer_pattern, cfg.moe_layer_freq)]
