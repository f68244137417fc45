"""Ling / Ring hybrid family (``model_type`` ``bailing_hybrid``: inclusionAI's
Ling-3.0-flash among others) — a decoder-only LM whose token mixer is Kimi
Delta Attention (KDA: Kimi Team, "Kimi Linear: An Expressive, Efficient
Attention Architecture", arXiv:2510.26692) in five layers of six and gated
latent attention (DeepSeek-V2 / V3's MLA) in the sixth; the leading dense
layers are followed by a sigmoid top-k mixture whose router chooses groups
before experts, beside a shared expert. Of a layer's heads and of its routed
experts this model may hold one chip's share.

The equations. ``config.json`` fixes the widths and the names; what goes
beyond it is Kimi Linear's paper and public code (``fla``'s
``KimiDeltaAttention`` with its ``safe_gate`` / ``lower_bound`` gate), the
DeepSeek-V3 family's modelling code and the Ling / Ring hybrid family's as
this repository's authors know them, marked *assumed* in
``benchmark/configs/ling-3.0-flash.json``. ``L`` positions, width ``d``, ``H``
heads of ``D = 128``, no bias anywhere::

    x = E[tokens]                          float32 residual stream
    per layer i:  x = x + Mixer(norm1(x));  x = x + FFN(norm2(x))     RMSNorm, eps 1e-6
      Mixer is latent attention where (i + 1) % 6 == 0 (``layer_types``), KDA otherwise
    KDA(h):
      q = silu(conv4(h W_q));  k = silu(conv4(h W_k));  v = silu(conv4(h W_v))
          causal depthwise convolutions of 4 taps over the sequence, a channel each
      q = q / sqrt(sum_head(q^2) + 1e-6) * D^-0.5;  k = k / sqrt(sum_head(k^2) + 1e-6)
      g_t = kda_lower_bound * sigmoid(exp(A_log_h) * (h_t W_f + dt_bias))      in (-5, 0), a channel,
          float32; alpha_t = exp(g_t)
      beta_t = sigmoid(h_t W_beta)                                             a head
      S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T     S in R^{D x D} a head,
          S_0 = 0, float32;  o_t = S_t^T q_t                                   (``ops/kda_scan.py``)
      y_t = W_o (RMSNorm_head(o_t) * sigmoid(h_t W_g))       one learned [D] weight for every head's norm
    MLA(h): ``models/deepseek_v3.py`` ``LatentAttention`` (no query low-rank path, rotary on the
      last 64 of a key's 192 columns in pairs, theta 6e6, the latent's own norm), then
      y = W_o concat_h(o_h * sigmoid(h W_gate)_h),  W_gate [d, H]              a head-wise gate
    FFN, layer < n_dense_layers:  W_down(silu(W_gate h) * W_up h), 6,144 wide
    FFN, else:  s = sigmoid(h.Wr [d, E]) in float32; with the bias b (in the choice only): a group's
      score is the sum of its two largest s + b among its E / n_group experts, the topk_group best
      groups stay, the top_k of s + b among their experts are chosen;
      w = s[chosen] / (sum over chosen of s + 1e-20) * routed_scaling_factor
      m = shared(h) + sum over chosen e of w_e . expert_e(h), every expert gated-SiLU
    logits = norm_f(x) W_head  (untied);  loss = mean next-token cross-entropy
    the expert bias, its rule and its balancing start: ``models/afmoe.py``'s, ``models/moe.py``

No multi-token-prediction module (the published ``mtp_loss_scaling_factor`` is
0: it adds nothing to the published training loss) and no clamp in the
experts (the published ``*_swiglu_limit_list`` is 0 in the early layers).

**One chip's share of the heads** (``heads_held`` of ``n_heads`` from
``first_head_held`` on, in both mixers): the projections of a KDA layer are
``d x heads_held D`` (``W_beta`` ``d x heads_held``), ``A_log``, ``dt_bias``
and the convolutions' taps are the held heads', ``W_o`` is ``heads_held D x
d``; latent attention's ``query``, ``kv_up``, ``gate`` and ``out`` likewise
(its latent and rotary key are every chip's alike). What the other chips'
heads would add to ``W_o``'s sum — a tensor-parallel layer's all-reduce — is
left out, in the program and in the reference alike. **One chip's share of
the experts** is ``models/moe.py`` ``RoutedShare``'s. No code stands in for
the absent chips.

What XLA keeps of a KDA mixer between the projections and the kernels sits
under named scopes: ``kda_qk_norm`` (the two L2 norms), ``kda_gate`` (the
decay and ``beta``) and ``kda_out_norm``. A head's sum of squares is a
product with 0 / 1 columns and so is its spread back over the head's
columns: the ``[B, L, H D]`` rows the convolutions wrote and the kernels read
are never laid out as ``[B, L, H, D]`` (PERF.md section 6, "PR 41").

Under ``remat`` every layer is a ``jax.checkpoint`` that keeps what the
recurrence's forward rule hands its backward (``o`` and one float32 state a
chunk and head), flash's ``o`` and log-sum-exp, latent attention's query,
latent and rotary key and the router's logits (:data:`KEPT`), and makes the
rest again from the residual stream: the projections, the convolutions, the
gates, the MLPs.

**The first layers' outputs are float32's** (:data:`PRECISE_LAYERS` 2,
*assumed*; ``models/jamba.py`` has the mechanism and its story, and the code
is that code: ``_precise_product``, ``_projection``, its ``GatedMLP``):
beside its ordinary forward the leading dense layer's KDA mixer and MLP, and
the second layer's KDA mixer, are computed once more with every product as
three bfloat16 passes and float32 between them, the kernels' operands too,
and the stream takes that value; the backward is the ordinary sublayer's
(``ordinary + stop_gradient(precise - ordinary)``). The embedding's rows are
0.02 wide and the first layer's two outputs some twenty times that, so its
output IS the stream: what its forward rounds off is rounded off the whole
signal, and every later layer's Jacobian, a KDA layer's more than a latent
layer's, is then taken at a point that far off; the second layer's mixer
still adds half of what the stream then is. Without them the whole gradient
stands 0.084-0.092 from the float32 reference's on the chip where the check
allows 0.05; by layer on the CPU at the published widths and 512 positions:
0.078 with none, 0.040 with the first, 0.029 with both, 0.028 with three
(PERF.md section 6, "PR 52"; ``tools/ling_gradcheck.py``).

Parameters and the residual stream are float32; the sublayers compute in
``dtype``; the decay, ``beta``, both norms and the state are float32; the
router reads the float32 normalised input at ``HIGHEST`` precision.
"""

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from autodist_tpu import telemetry
from autodist_tpu.models.common import RMSNorm, head_columns
from autodist_tpu.models.decoder import Decoder, init_params, make_loss_fn  # noqa: F401
from autodist_tpu.models.deepseek_v3 import (KEPT_LATENT, KEPT_QUERY,
                                             KEPT_ROPE_KEY, LatentAttention)
from autodist_tpu.models.jamba import (GatedMLP as PreciseGatedMLP,
                                       _precise_product, _projection)
from autodist_tpu.models.moe import (  # noqa: F401 — the mixture's, under this family's names
    _INIT, KEPT_ROUTER_LOGITS, GatedMLP, RoutedShare, _dense, balance_expert_bias,
    balanced_optimizer as make_optimizer, check_share, expert_loads)
from autodist_tpu.models.transformer_lm import synthetic_batch  # noqa: F401 — re-exported
from autodist_tpu.ops.flash_attention import KEPT_NAME as KEPT_FLASH
from autodist_tpu.ops.kda_scan import KEPT_NAME as KEPT_KDA, kda_scan
from autodist_tpu.ops.short_conv import conv_silu

KDA, MLA = "kda", "mla"
# What a checkpointed layer keeps for its backward (module docstring)
KEPT = (KEPT_KDA, KEPT_FLASH, KEPT_QUERY, KEPT_LATENT, KEPT_ROPE_KEY,
        KEPT_ROUTER_LOGITS)
_HIGHEST = jax.lax.Precision.HIGHEST
# Leading layers whose output is computed a second time to float32's
# precision (module docstring; PERF.md section 6, "PR 52")
PRECISE_LAYERS = 2


@dataclasses.dataclass(frozen=True)
class BailingHybridConfig:
    """Defaults are Ling-3.0-flash's published sizes, every head and expert
    held."""
    vocab_size: int = 157184
    d_model: int = 2560
    n_layers: int = 42
    layer_group_size: int = 6         # latent attention where (i + 1) % this == 0
    layer_types: Optional[Tuple[str, ...]] = None     # None: by the rule above
    n_heads: int = 32                 # a layer's heads, both mixers ...
    heads_held: int = 32              # ... those whose projections live here ...
    first_head_held: int = 0          # ... from this one on
    head_dim: int = 128               # a KDA head's keys and values
    conv_kernel: int = 4              # short_conv_kernel_size
    kda_lower_bound: float = -5.0
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    n_dense_layers: int = 2           # first_k_dense_replace
    d_ff: int = 6144                  # the dense layers' width
    d_expert: int = 768               # one expert's width
    d_shared: int = 768               # the shared expert's
    n_experts_routed: int = 512       # the router's width
    experts_held: int = 512           # experts whose banks live here ...
    first_expert_held: int = 0        # ... from this one on
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    rows_bound: Optional[int] = None  # held rows a pass computes; None: tokens x top_k
    route_norm: bool = True
    route_scale: float = 2.5
    route_eps: float = 1e-20
    load_balance_coeff: float = 1e-3
    rope_theta: float = 6e6
    rms_eps: float = 1e-6
    max_len: int = 262144
    dtype: Any = jnp.bfloat16         # what the sublayers compute in
    attention_impl: str = "dot"       # "dot" | "flash"
    kda_impl: str = "xla"             # "xla" | "pallas": the recurrence and the convolutions
    kda_chunk: int = 64
    fused_head: bool = False          # pallas head + loss (ops/fused_xent)
    remat: bool = False               # jax.checkpoint around every layer, keeping KEPT

    def __post_init__(self):
        if self.attention_impl not in ("dot", "flash"):
            raise ValueError(f"Unknown attention_impl {self.attention_impl!r}; "
                             f"valid: 'dot', 'flash'")
        if self.kda_impl not in ("xla", "pallas"):
            raise ValueError(f"Unknown kda_impl {self.kda_impl!r}; "
                             f"valid: 'xla', 'pallas'")
        if self.qk_rope_head_dim % 2 or self.qk_nope_head_dim % 2:
            raise ValueError("the key's two parts must be even")
        if not 0 < self.heads_held <= self.n_heads - self.first_head_held \
                or self.first_head_held < 0:
            raise ValueError(
                f"heads [{self.first_head_held}, {self.first_head_held} + "
                f"{self.heads_held}) are not among the layer's {self.n_heads}")
        if len(self.kinds) != self.n_layers or set(self.kinds) - {KDA, MLA}:
            raise ValueError(f"layer_types {self.layer_types} are not "
                             f"{self.n_layers} of {KDA!r} / {MLA!r}")
        if self.n_experts_routed % self.n_group \
                or not 1 <= self.topk_group <= self.n_group:
            raise ValueError("the router's width must be n_group equal groups, "
                             "of which topk_group stay")
        check_share(self)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Each layer's mixer."""
        if self.layer_types is not None:
            return tuple(self.layer_types)
        return tuple(MLA if (i + 1) % self.layer_group_size == 0 else KDA
                     for i in range(self.n_layers))


def a_log_init(key, shape, dtype=jnp.float32):
    """``log(uniform(1, 16))`` a head: Kimi Linear's code."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of ``dt`` log-uniform in [0.001, 0.1], a channel:
    Kimi Linear's code (Mamba's)."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                 * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    dt = jnp.maximum(dt, 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


def conv_init(key, shape, dtype=jnp.float32):
    """A depthwise convolution's taps ``[channels, K]``: uniform in ``+-
    K^-0.5``, torch's ``Conv1d`` default at one input channel a group."""
    bound = shape[-1] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _head_sums(x, columns):
    """``[B, L, H D] -> [B, L, H]``: each head's sum over its columns, a
    product with 0 / 1 columns at full precision (module docstring)."""
    return jnp.einsum("blc,hc->blh", x, columns, precision=_HIGHEST)


def _over_columns(y, columns):
    """``[B, L, H] -> [B, L, H D]``: a head's value over its columns."""
    return jnp.einsum("blh,hc->blc", y, columns, precision=_HIGHEST)


def _float32_product(h, kernel, precise: bool = False):
    """``h.W`` with a float32 result (the decay's and ``beta``'s
    pre-activations are not rounded to ``h.dtype``): on ``h``'s own bfloat16
    operands, or under ``precise`` to float32's precision
    (``models/jamba.py`` ``_precise_product``: three bfloat16 passes)."""
    if precise:
        return _precise_product(h.reshape(-1, h.shape[-1]), kernel).reshape(
            *h.shape[:-1], kernel.shape[-1])
    return jnp.einsum("bld,dc->blc", h, kernel.astype(h.dtype),
                      preferred_element_type=jnp.float32,
                      precision=_HIGHEST if h.dtype == jnp.float32 else None)


class KimiDeltaAttention(nn.Module):
    """KDA over the heads held here (module docstring): the projections of
    ``heads_held`` heads, their convolutions, decay and ``beta``, the
    recurrence, the output norm under its gate, and the held heads' part of
    the output projection. ``precise``: float32 between the products, three
    bfloat16 passes for each, the kernels' operands float32 too."""
    config: BailingHybridConfig

    @nn.compact
    def __call__(self, h, precise: bool = False):
        cfg = self.config
        b, length, _ = h.shape
        held, d = cfg.heads_held, cfg.head_dim
        wide = held * d
        dtype = jnp.float32 if precise else cfg.dtype
        project = lambda features, name: _projection(  # noqa: E731
            features, dtype, name, precise)
        raw = [project(wide, name)(h) for name in ("query", "key", "value")]
        taps = [self.param(f"{name}_conv", conv_init, (wide, cfg.conv_kernel),
                           jnp.float32) for name in ("query", "key", "value")]
        w_decay = self.param("decay", _INIT, (cfg.d_model, wide), jnp.float32)
        a_log = self.param("A_log", a_log_init, (held,), jnp.float32)
        dt_bias = self.param("dt_bias", dt_bias_init, (wide,), jnp.float32)
        w_beta = self.param("beta", _INIT, (cfg.d_model, held), jnp.float32)
        gate = project(wide, "gate")(h)
        norm = self.param("out_norm", nn.initializers.ones, (d,), jnp.float32)
        out = project(cfg.d_model, "out")
        if self.is_initializing():
            # Shapes are all that init needs: no kernel is compiled for the
            # handful of positions it runs on.
            return out(raw[2])
        telemetry.gauge("kda.heads_held").set(held)
        no_bias = jnp.zeros((wide,), jnp.float32)
        q, k, v = (conv_silu(x, w, no_bias, impl=cfg.kda_impl).astype(dtype)
                   for x, w in zip(raw, taps))
        columns = head_columns(held, d)
        with jax.named_scope("kda_qk_norm"):
            def unit(x, scale):
                x = x.astype(jnp.float32)
                norms = jax.lax.rsqrt(_head_sums(jnp.square(x), columns) + 1e-6)
                return (x * _over_columns(norms * scale, columns)).astype(dtype)
            q, k = unit(q, d ** -0.5), unit(k, 1.0)
        with jax.named_scope("kda_gate"):
            speed = jnp.repeat(jnp.exp(a_log), d)                   # [H D]
            g = cfg.kda_lower_bound * jax.nn.sigmoid(
                speed * (_float32_product(h, w_decay, precise) + dt_bias))
            beta = jax.nn.sigmoid(_float32_product(h, w_beta, precise))
        o = kda_scan(q, k, v, g, beta, chunk=cfg.kda_chunk, impl=cfg.kda_impl)
        with jax.named_scope("kda_out_norm"):
            o = o.astype(jnp.float32)
            rms = jax.lax.rsqrt(_head_sums(jnp.square(o), columns) / d
                                + cfg.rms_eps)
            y = (o * _over_columns(rms, columns) * jnp.tile(norm, held)
                 * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dtype)
        return out(y)


def float32_valued(sublayer, h, dtype, precise: bool):
    """``sublayer(h)`` on ``h`` cast to ``dtype``; under ``precise`` with the
    value of ``sublayer(h, True)`` (the float32 ``h``, every product to
    float32's precision) and the ordinary call's derivative."""
    out = sublayer(h.astype(dtype))
    if precise:
        out = out + jax.lax.stop_gradient(sublayer(h, True) - out)
    return out


class BailingHybridBlock(nn.Module):
    """``x + Mixer(RMSNorm(x))``, then ``+ FFN(RMSNorm(.))``; ``(x, the
    layer's bias term)``. ``kind``: the mixer; ``dense``: the FFN.
    ``precise`` (a KDA layer): the mixer's output and, with it, the dense
    MLP's take the value computed a second time to float32's precision and
    keep the ordinary sublayer's derivative; a mixture is left as it is (its
    choices are made on the float32 stream already)."""
    config: BailingHybridConfig
    kind: str
    dense: bool
    precise: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        if self.precise and self.kind != KDA:
            raise ValueError("a precise layer is a KDA layer")

        h = RMSNorm(cfg.rms_eps, jnp.float32, name="ln_attn")(x)
        if self.kind == MLA:
            x = x + LatentAttention(cfg, heads_held=cfg.heads_held,
                                    head_gate=True, name="attn")(
                                        h.astype(cfg.dtype))
        else:
            x = x + float32_valued(KimiDeltaAttention(cfg, name="kda"), h,
                                   cfg.dtype, self.precise)
        h = RMSNorm(cfg.rms_eps, jnp.float32, name="ln_mlp")(x)
        if self.dense:
            mlp = PreciseGatedMLP(cfg.d_ff, cfg.dtype, name="mlp")
            return (x + float32_valued(mlp, h, cfg.dtype, self.precise),
                    jnp.zeros((), jnp.float32))
        m, bias_term = RoutedShare(cfg, cfg.d_shared, name="moe")(h)
        return x + m, bias_term


class BailingHybrid(Decoder):
    """``tokens [B, L] -> (logits or hidden, the expert layers' bias terms
    summed: ``models/afmoe.py``'s docstring)``."""
    config: BailingHybridConfig
    block = BailingHybridBlock
    kept = KEPT

    def layers(self):
        cfg = self.config
        # in float32 every layer is exact already
        precise = PRECISE_LAYERS if cfg.dtype != jnp.float32 else 0
        return [(kind, i < cfg.n_dense_layers, i < precise and kind == KDA)
                for i, kind in enumerate(cfg.kinds)]
