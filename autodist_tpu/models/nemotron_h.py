"""Nemotron-H (``model_type`` ``nemotron_h``: NVIDIA's Nemotron-3-Nano-30B-A3B)
— a decoder-only LM whose every layer is ONE mixer under a pre-norm and a
residual: a Mamba-2 state-space layer (``M``), a sigmoid top-k mixture of
``relu2`` experts beside a shared expert (``E``), of which this layer may
hold one chip's share, or grouped-query attention (``*``), in the order
``hybrid_override_pattern`` spells.

The equations, from the published ``config.json`` and the Mamba-2 paper (Dao
& Gu 2024; what the config does not carry is marked *assumed*, and listed
with its source in ``benchmark/configs/nemotron-3-nano-30b-a3b.json``). ``T``
tokens, width ``d``; Mamba-2: ``H`` heads of ``P`` (``d_inner = H P``), ``G``
groups of state ``N``, ``K`` taps; attention: ``H_a`` query heads over
``H_kv`` KV heads of ``head_dim``::

    x0 = E[tokens]                                       (no scale)
    per layer l, kind = pattern[l] in {M, E, *}:  x = x + mixer_kind(RMSNorm(x))
      M:  [z | xBC | dt] = h.W_in [d, 2 d_inner + 2 G N + H]           (no bias; assumed: this order)
          xBC_t = silu(sum_{j<K} w[:, j] * xBC_{t-(K-1)+j} + b)       depthwise, causal, w [d_inner + 2GN, K]
          [x | B | C] = xBC   (d_inner | G N | G N);  x: H heads of P; B, C: G groups of N
          dt = softplus(dt + dt_bias)  float32;  A = -exp(A_log)  a head;  a_t = dt_t A
          per head h of group g = h // (H / G):
            S_t = exp(a_t) S_{t-1} + dt_t x_t B_t^T      S in R^{P x N}, S_0 = 0 at every sequence's start
            y_t = S_t C_t + D_h x_t
          y = RMSNorm_grouped(y * silu(z))               groups of d_inner / G, one weight [d_inner]
          out = y.W_out [d_inner, d]
      E:  s = sigmoid(h.Wr [d, E]) in float32
          chosen = top_k(s + b)         b = expert_bias [E], in the choice only, no gradient
          w = s[chosen] / (sum over chosen of s + 1e-20) * route_scale          (norm_topk_prob; assumed eps)
          out = W_down,s relu(W_up,s h)^2  (the shared expert, width d_shared)
                + sum over chosen e of w_e . W_down,e relu(W_up,e h)^2          (mlp_hidden_act relu2: no gate, no bias)
      *:  q, k, v = h.Wq [d, H_a*hd], h.Wk [d, H_kv*hd], h.Wv        (no bias, no q/k norm)
          s_ij = q_i.k_j / sqrt(hd) for j <= i;  query head n reads KV head n // (H_a / H_kv)
          out = softmax_j(s).v . Wo [H_a*hd, d]
          no positional embedding: the family's published description (Nemotron-H) gives its
          attention layers none, the state-space layers carry the order (assumed; ``config.json``
          has a ``rope_theta`` this file does not read)
    logits = RMSNorm_f(x).W_head  (untied);  loss = mean next-token cross-entropy
    after each optimizer step, per expert layer, c_e = rows expert e received in the step:
      delta = load_balance_coeff * sign(mean(c) - c_e);  b += delta - mean(delta)        (assumed)

Initialisation (assumed, the Mamba-2 reference's): ``A_log = log(1..H)``, ``D``
ones, ``dt_bias`` the inverse softplus of ``dt`` drawn log-uniformly in
``[time_step_min, time_step_max]`` and floored at ``time_step_floor``, the
convolution uniform in ``+-1/sqrt(K)``, every matrix normal(0.02), and under
``rescale_prenorm_residual`` each mixer's output matrix divided by
``sqrt(n_layers)``.

**One chip's share**, **the expert bias on the normal path** and its start
from the balancing rule alone are ``models/afmoe.py``'s, word for word, and
the code is the same code: ``models/moe.py`` ``RoutedShare`` (told the
expert's form, ``relu2``: two banks and no gate, and the shared expert's
width), ``balanced_optimizer``, ``balance_expert_bias``. The shared expert,
the Mamba-2 and attention layers and the router are what every rank computes
alike. The stack, the loss and the init are ``models/decoder.py``'s.

The scan is one operator, ``ops/ssd_scan.py`` ``ssd_scan``, chunked
(``chunk_size`` positions a chunk, one ``[P, N]`` state a head carried between
chunks), the convolution before it ``ops/short_conv.py`` ``conv_silu`` and the
gated norm after it ``ops/gated_norm.py`` ``gated_norm``: all three plain
(``ssm_impl="xla"``) or each as two Pallas kernels (``"pallas"``). Between
``in_proj`` and ``out_proj`` the mixer holds ONE layout, ``[B, L, columns]``
rows (:class:`Mamba2`): the convolution reads ``xBC`` as columns ``d_inner :
d_inner + conv_dim`` of ``in_proj``'s output, the scan reads ``x``, ``B``,
``C`` as column blocks of the convolution's output and writes ``y`` as ``[B,
L, d_inner]`` rows, the norm reads those and ``z`` as the first ``d_inner``
columns of ``in_proj``'s output; every cut is on a lane tile's edge (4,096;
10,240; 4,096 and 5,120 within ``xBC``; a run's 512, a group's 128).

Under ``remat`` every layer is a ``jax.checkpoint`` (``models/decoder.py``)
whose policy keeps a short list of named values (:data:`KEPT`) and makes the
rest again from the residual stream. Without any checkpoint the benchmark's
cell needs 17.1 GiB of a 15.75 GiB chip; with a bare one it has 3 GiB free and
its backward runs every forward kernel and matmul a second time. The list is
what is dearest to make again for the bytes it takes to keep (PERF.md
section 6, "PR 36", has the milliseconds a MiB of each): attention's q / k /
v and what flash's forward rule hands its backward (``o``, the log-sum-exp),
the router's logits (six bfloat16 passes), a Mamba-2 layer's ``in_proj``
product (layer 0's float32 one at three passes first), the shared expert's
``up`` product, what pass 0 of one chip's share of the routed experts makes
for its transpose (the gathered rows and the ``up`` product) and what the
scan's forward rule makes (``y`` and one state a chunk and head). The ceiling
that ends the list is the cell's compiled step at 12.4 GiB
(``benchmark/rehearse.py`` says it before any chip time): the convolution's
output beside the rest passes it (12.50) and in the scan's place it buys the
same time, so it is made again. A mixer's *last* product (``out_proj``,
attention's ``out``, the shared and the routed ``down``) feeds only the
layer's output, which the next layer keeps as its own input, so no backward
makes it again; norms, softplus, the gated norm, ``relu2`` and casts are made
again, cheap for their bytes. The names inside the kernels' ``custom_vjp``
forward rules (``ops/flash_attention.py``, ``ops/ssd_scan.py``) and in
``models/moe.py`` are the identity wherever no checkpoint lists them: the
policy is this model's, so no other caller's program changes.

Parameters and the residual stream are float32; the mixers compute in
``dtype`` (under ``exact_first_layer`` layer 0's in float32 around a scan on
``dtype`` operands: :class:`Mamba2` says why); ``dt``, ``a`` and every
``exp`` of the scan are float32; the router
reads the float32 normalised input at ``HIGHEST`` precision, as OLMoE's and
AFMoE's do.
"""

import dataclasses
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from autodist_tpu.models.common import RMSNorm
from autodist_tpu.models.decoder import Decoder, init_params, make_loss_fn  # noqa: F401
from autodist_tpu.models.moe import (  # noqa: F401 — the mixture's, under this family's names
    KEPT_PASS, KEPT_ROUTER_LOGITS, KEPT_UP, PlainMLP, RoutedShare, _INIT,
    balance_expert_bias, balanced_optimizer as make_optimizer, check_share,
    expert_loads, sown_loads)
from autodist_tpu.models.transformer_lm import (  # noqa: F401 — synthetic_batch re-exported
    causal_mask, dot_product_attention, synthetic_batch)
from autodist_tpu.ops.flash_attention import KEPT_NAME as KEPT_FLASH
from autodist_tpu.ops.gated_norm import gated_group_norm, gated_norm  # noqa: F401
from autodist_tpu.ops.short_conv import conv_silu
from autodist_tpu.ops.ssd_scan import (IMPLS as SSM_IMPLS, KEPT_NAME as KEPT_SCAN,
                                       ssd_scan)

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

KEPT_IN_PROJ = "ssm_in_proj"          # a Mamba-2 layer's [z | xBC | dt]
KEPT_QKV = "attn_qkv"                 # attention's three projections
# What a checkpointed layer keeps for its backward (module docstring), the
# dearest to make again for its bytes first.
KEPT = (KEPT_QKV, KEPT_FLASH, KEPT_ROUTER_LOGITS, KEPT_IN_PROJ, KEPT_UP,
        KEPT_PASS, KEPT_SCAN)


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """Defaults are Nemotron-3-Nano-30B-A3B's published sizes, every expert
    held."""
    vocab_size: int = 131072
    d_model: int = 2688
    pattern: str = PUBLISHED_PATTERN  # hybrid_override_pattern: M, E, * a layer
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    d_state: int = 128                # ssm_state_size
    conv_kernel: int = 4
    chunk: int = 128                  # chunk_size of the scan
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    d_expert: int = 1856              # one routed expert's width
    d_shared: int = 3712              # the shared expert's
    n_experts_routed: int = 128       # the router's width
    experts_held: int = 128           # experts whose banks live here ...
    first_expert_held: int = 0        # ... from this one on
    top_k: int = 6
    rows_bound: Optional[int] = None  # held rows a pass computes; None: tokens x top_k
    route_norm: bool = True           # norm_topk_prob
    route_scale: float = 2.5          # routed_scaling_factor
    route_eps: float = 1e-20          # in the normaliser of the chosen scores
    load_balance_coeff: float = 1e-3
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    rescale_prenorm_residual: bool = True
    rms_eps: float = 1e-5
    max_len: int = 262144
    dtype: Any = jnp.bfloat16         # what the mixers compute in
    attention_impl: str = "dot"       # "dot" | "flash"
    ssm_impl: str = "xla"             # "xla" | "pallas": the scan and its convolution
    fused_head: bool = False          # pallas head + loss (ops/fused_xent)
    remat: bool = False               # jax.checkpoint around every layer
    exact_first_layer: bool = False   # layer 0 (Mamba-2) in float32, see Mamba2

    def __post_init__(self):
        if self.attention_impl not in ("dot", "flash"):
            raise ValueError(f"Unknown attention_impl {self.attention_impl!r}; "
                             f"valid: 'dot', 'flash'")
        if self.ssm_impl not in SSM_IMPLS:
            raise ValueError(f"Unknown ssm_impl {self.ssm_impl!r}; "
                             f"valid: {SSM_IMPLS}")
        unknown = set(self.pattern) - {MAMBA, EXPERTS, ATTENTION}
        if unknown or not self.pattern:
            raise ValueError(f"pattern must be of {MAMBA!r}, {EXPERTS!r} and "
                             f"{ATTENTION!r}; got {sorted(unknown)}")
        if self.n_heads % self.n_kv_heads or self.mamba_heads % self.n_groups:
            raise ValueError("n_heads must divide over n_kv_heads, "
                             "mamba_heads over n_groups")
        check_share(self)
        if self.exact_first_layer and self.pattern[0] != MAMBA:
            raise ValueError("exact_first_layer is the Mamba-2 layer's; the "
                             f"pattern starts with {self.pattern[0]!r}")

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim


def _out_proj(config: NemotronHConfig, name: str, dtype=None,
              precision=None) -> nn.Dense:
    """A mixer's output matrix: normal(0.02), divided by ``sqrt(n_layers)``
    under ``rescale_prenorm_residual``."""
    std = 0.02 / (math.sqrt(config.n_layers)
                  if config.rescale_prenorm_residual else 1.0)
    return nn.Dense(config.d_model, use_bias=False, dtype=dtype or config.dtype,
                    param_dtype=jnp.float32, precision=precision,
                    kernel_init=nn.initializers.normal(std), name=name)


def _in_proj(features: int, config: NemotronHConfig, name: str, dtype=None,
             precision=None) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype or config.dtype,
                    param_dtype=jnp.float32, precision=precision,
                    kernel_init=_INIT, name=name)


def _dt_bias_init(config: NemotronHConfig):
    def init(key, shape, dtype=jnp.float32):
        low, high = math.log(config.time_step_min), math.log(config.time_step_max)
        dt = jnp.exp(jax.random.uniform(key, shape, dtype) * (high - low) + low)
        dt = jnp.maximum(dt, config.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1
    return init


def _uniform(bound: float):
    return lambda key, shape, dtype=jnp.float32: jax.random.uniform(
        key, shape, dtype, -bound, bound)


class Mamba2(nn.Module):
    """The state-space mixer: input projection to ``[z | xBC | dt]``, the
    depthwise causal convolution with bias and SiLU, the chunked scan, the
    gated grouped RMSNorm, output projection. ``h``: ``[B, L, d_model]``;
    inside, ``[z | xBC | dt]`` is ``[B, L, 2 d_inner + 2 G N + H]``, the
    convolution's ``[x | B | C]`` ``[B, L, d_inner + 2 G N]``, ``dt`` ``[B,
    L, H]`` float32, ``y`` and the normed rows ``[B, L, d_inner]``: the
    operators are handed these arrays whole and told where their columns
    lie, never a slice or another shape of them.

    ``exact``: everything but the scan's products in float32, the two
    projections at ``Precision.HIGH`` (three bfloat16 passes). The first
    layer's: the embedding is a twentieth of what that layer adds to it, so
    its output *is* the residual stream every later router reads, and its
    rounding (0.5% in bfloat16) moves one token in twenty across a top-k
    boundary in every expert layer above (PERF.md section 6, "PR 35")."""
    config: NemotronHConfig
    exact: bool = False

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        dtype, precision = ((jnp.float32, jax.lax.Precision.HIGH) if self.exact
                            else (cfg.dtype, None))
        heads, g, n = cfg.mamba_heads, cfg.n_groups, cfg.d_state
        d_inner, conv_dim = cfg.d_inner, cfg.d_inner + 2 * g * n
        taps = self.param("conv", _uniform(cfg.conv_kernel ** -0.5),
                          (conv_dim, cfg.conv_kernel), jnp.float32)
        conv_bias = self.param("conv_bias", _uniform(cfg.conv_kernel ** -0.5),
                               (conv_dim,), jnp.float32)
        a_log = self.param("A_log", lambda *_: jnp.log(
            jnp.arange(1, heads + 1, dtype=jnp.float32)))
        d_skip = self.param("D", nn.initializers.ones, (heads,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init(cfg), (heads,), jnp.float32)
        scale = self.param("norm", nn.initializers.ones, (d_inner,), jnp.float32)
        # init runs the plain paths: shapes are all it needs
        impl = "xla" if self.is_initializing() else cfg.ssm_impl
        # one layout from here to ``out_proj`` (module docstring): a split or
        # a reshape here is a pass over memory on the chip, 2 GB a layer
        # before PR 49 (PERF.md section 6)
        with jax.named_scope("ssm.in_proj"):
            zxbcdt = checkpoint_name(
                _in_proj(d_inner + conv_dim + heads, cfg, "in_proj", dtype,
                         precision)(h), KEPT_IN_PROJ)
        with jax.named_scope("ssm.conv"):
            xbc = conv_silu(zxbcdt, taps, conv_bias, impl,
                            at=d_inner).astype(cfg.dtype)
        with jax.named_scope("ssm.scan"):
            dt = jax.nn.softplus(
                zxbcdt[..., d_inner + conv_dim:].astype(jnp.float32) + dt_bias)
            y = ssd_scan(xbc, dt, -jnp.exp(a_log), None, None, d_skip,
                         chunk=cfg.chunk, impl=impl, groups=(g, n))
        with jax.named_scope("ssm.gate_norm"):
            y = gated_norm(y, zxbcdt, scale, g, cfg.rms_eps, dtype, impl)
        with jax.named_scope("ssm.out_proj"):
            return _out_proj(cfg, "out_proj", dtype, precision)(y)


class GroupedAttention(nn.Module):
    """Causal attention, ``H_a`` query heads over ``H_kv`` KV heads: no bias,
    no norm on q or k, no positional embedding, no gate."""
    config: NemotronHConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        b, length, _ = h.shape
        wide, narrow = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        q = checkpoint_name(_in_proj(wide, cfg, "query")(h), KEPT_QKV)
        k = checkpoint_name(_in_proj(narrow, cfg, "key")(h), KEPT_QKV)
        v = checkpoint_name(_in_proj(narrow, cfg, "value")(h), KEPT_QKV)
        if cfg.attention_impl == "flash" and not self.is_initializing():
            from autodist_tpu.ops.flash_attention import flash_attention
            # no position is turned into q or k here: the projections' own
            # rows go in and the result's rows come out, read and written by
            # the kernels where they lie
            ctx = flash_attention(q, k, v, causal=True,
                                  heads=(cfg.n_heads, cfg.n_kv_heads))
        else:
            heads = lambda t, n: t.reshape(b, length, n, cfg.head_dim)  # noqa: E731
            q, k, v = heads(q, cfg.n_heads), heads(k, cfg.n_kv_heads), \
                heads(v, cfg.n_kv_heads)
            group = cfg.n_heads // cfg.n_kv_heads
            ctx = dot_product_attention(
                q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2),
                causal_mask(length, cfg.dtype), cfg.dtype)
        return _out_proj(cfg, "out")(ctx.reshape(b, length, wide))


class NemotronHBlock(nn.Module):
    """``x + mixer(RMSNorm(x))`` for one mixer; ``(x, the layer's bias term)``."""
    config: NemotronHConfig
    kind: str
    exact: bool = False               # a Mamba-2 layer's (Mamba2)

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        bias_term = jnp.zeros((), jnp.float32)
        if self.kind == EXPERTS:
            h = RMSNorm(cfg.rms_eps, jnp.float32, name="norm")(x)
            m, bias_term = RoutedShare(cfg, cfg.d_shared, "relu2",
                                       name="moe")(h)
        elif self.kind == MAMBA:
            h = RMSNorm(cfg.rms_eps, jnp.float32 if self.exact else cfg.dtype,
                        name="norm")(x)
            m = Mamba2(cfg, self.exact, name="mamba")(h)
        else:
            h = RMSNorm(cfg.rms_eps, cfg.dtype, name="norm")(x)
            m = GroupedAttention(cfg, name="attn")(h)
        return x + m, bias_term


class NemotronH(Decoder):
    """``tokens [B, L] -> (logits or hidden, the expert layers' bias terms
    summed: ``models/afmoe.py``'s docstring)``."""
    config: NemotronHConfig
    block = NemotronHBlock
    final_norm = "norm_f"
    kept = KEPT

    def layers(self):
        return [(kind, self.config.exact_first_layer and i == 0)
                for i, kind in enumerate(self.config.pattern)]
