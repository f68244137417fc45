"""Jamba (``model_type`` ``jamba``: AI21's Jamba2-3B) — a decoder-only LM
whose every layer is a mixer AND a dense gated-SiLU MLP, each under a pre-norm
and a residual: the mixer is a Mamba-1 state-space layer, or, in one layer of
every ``attn_layer_period``, causal attention with many query heads over few
KV heads and no positional encoding of any kind.

The equations, from the published ``config.json`` and HF's
``modeling_jamba.py`` (what the config does not carry is marked *assumed*, and
listed with its source in ``benchmark/configs/jamba2-3b.json``). Width ``d``,
``E = mamba_expand d`` channels, state ``N``, rank ``R``, ``K`` taps::

    x0 = E[tokens]                                         (no scale)
    per layer i:
      attention iff i % attn_layer_period == attn_layer_offset      (HF's layers_block_type rule)
      h = x + mixer(RMSNorm_in(x));  x = h + W_down(silu(W_gate u) * W_up u),  u = RMSNorm_ff(h)
      Mamba-1:
        [x | z]   = u W_in [d, 2E]                         (no bias)
        x         = silu(sum_{j<K} w[:, j] x_{t-(K-1)+j} + b)        depthwise, causal, w [E, K]
        [r | B | C] = x W_x [E, R + 2N]
        r, B, C   = RMSNorm_dt(r), RMSNorm_B(B), RMSNorm_C(C)        Jamba's three inner norms
        dt        = softplus(r W_dt [R, E] + b_dt)         float32
        A         = -exp(A_log) [E, N]
        s_t       = exp(dt_t (x) A) . s_{t-1} + (dt_t . x_t) (x) B_t       s [E, N], s_0 = 0 a sequence
        y_t       = s_t C_t + D . x_t
        out       = (y . silu(z)) W_out [E, d]             (no bias)
      attention: q = u Wq [d, H hd], k = u Wk [d, H_kv hd], v = u Wv        (no bias, no norm, NO rotary turn)
        s_ij = q_i.k_j / sqrt(hd) for j <= i;  query head n reads KV head n // (H / H_kv)
        out  = softmax_j(s).v . Wo [H hd, d]
    logits = RMSNorm_f(x) E^T  (the tied table);  loss = mean next-token cross-entropy

``num_experts`` is 1 in the published file, so every layer's MLP is dense
whatever ``expert_layer_period`` says; this file has no routed layer.

Departures from HF's file, each *assumed*: the initialisers are Mamba-1's
(``A_log = log(1..N)`` a channel, ``D`` ones, ``dt`` bias the inverse softplus
of a step drawn log-uniformly in ``[time_step_min, time_step_max]`` and floored,
``W_dt`` uniform in ``+-R^-1/2``, the convolution uniform in ``+-K^-1/2``, every
other matrix normal(0.02)) where HF draws all from normal(0.02) and zeros;
the three inner norms read ``x W_x`` in float32 (the product's own
accumulator) and ``dt``'s pre-activation is float32 too: a bfloat16 ``dt``
moves every decay ``exp(dt A)`` of 16,384 steps.

The scan is one operator, ``ops/selective_scan.py`` ``selective_scan``
(chunked, one float32 ``[E, N]`` state carried between chunks, never a state
a token), and the convolution before it ``ops/short_conv.py`` ``conv_silu``:
both plain (``ssm_impl="xla"``) or each as two Pallas kernels (``"pallas"``).
The scan's kernels read ``x`` and ``dt`` as the ``[B, L, E]`` rows the
convolution and ``W_dt`` wrote and hand ``y`` (backward: ``dx``, ``ddt``) back
the same way, so nothing is laid out anew between the mixer's products and
its three scan calls a step. ``silu(z)`` gating is outside the scan (XLA fuses
it into ``W_out``'s operand), the ``D`` term inside.

Under ``remat`` every layer is a ``jax.checkpoint`` (``models/decoder.py``)
whose policy keeps the values named in :data:`KEPT` and makes the rest again
from the residual stream. At the layer's edge the residual stream is held to
the batch sharding (``parallel/mesh.py`` ``constrain_batch``), so that under
``strategy.FullySharded`` the partitioner gathers a layer's weights where the
layer uses them, and again under its checkpoint, rather than moving
activations.

Parameters and the residual stream are float32; the mixers and the MLP
compute in ``dtype``; ``dt``, ``A`` and the scan's arithmetic are float32.

**The first layer's output is float32's** (:data:`PRECISE_LAYERS`, *assumed*):
beside its ordinary forward it is computed once more with every product as
three bfloat16 passes (:func:`_precise_product`) and float32 between them,
the kernels' operands too, and the stream takes that value; its backward, and
the forward made again for it, are the ordinary layer's (``ordinary +
stop_gradient(precise - ordinary)``). The embedding's rows are 0.02 wide
(normal(0.02), *assumed*) and the first layer's two outputs 0.2 and 0.6, so
that layer's output IS the stream: what its forward rounds off is rounded off
the whole signal, and every later layer's Jacobian is then taken at a point
that far off, where a later layer only rounds the share it adds. Of the whole
gradient's distance to the float32 reference the first layer's forward is
four fifths of the square (PERF.md section 6, "PR 43": 0.049 of the check's
0.05 at 14 layers without this, 0.0504 on one seed).
"""

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from autodist_tpu import telemetry
from autodist_tpu.models.common import RMSNorm
from autodist_tpu.models.decoder import Decoder, init_params, make_loss_fn  # noqa: F401
from autodist_tpu.models.moe import _INIT, _dense
from autodist_tpu.models.nemotron_h import KEPT_QKV, _dt_bias_init, _uniform
from autodist_tpu.models.transformer_lm import (  # noqa: F401 — synthetic_batch re-exported
    causal_mask, dot_product_attention, synthetic_batch)
from autodist_tpu.ops.flash_attention import KEPT_NAME as KEPT_FLASH
from autodist_tpu.ops.selective_scan import (DEFAULT_CHUNK, IMPLS as SSM_IMPLS,
                                             selective_scan)
from autodist_tpu.ops.short_conv import conv_silu
from autodist_tpu.parallel.mesh import constrain_batch

KEPT_X_PROJ = "ssm_x_proj"            # a Mamba-1 layer's [r | B | C]
# What a checkpointed layer keeps for its backward, the dearest to make again
# for its bytes first: attention's q / k / v, what flash's forward rule hands
# its backward, the narrow x W_x product (192 columns). The list ends where
# the four-chip cell's ceiling does (PERF.md section 6, "PR 43": 11.55 of
# 15.75 GiB a chip with these; the MLP's gate and up products, the next
# dearest, are 7 GiB more at 16,384 positions).
KEPT = (KEPT_QKV, KEPT_FLASH, KEPT_X_PROJ)
# The layers, from the first, whose output is computed to float32's precision
# (the docstring's last paragraph); the second layer's share of the squared
# distance is a tenth of the first's.
PRECISE_LAYERS = 1


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    """Defaults are AI21-Jamba2-3B's published sizes."""
    vocab_size: int = 65536
    d_model: int = 2560
    n_layers: int = 28
    attn_period: int = 14             # attn_layer_period
    attn_offset: int = 7              # attn_layer_offset
    mamba_expand: int = 2
    d_state: int = 16                 # mamba_d_state
    dt_rank: int = 160                # mamba_dt_rank
    conv_kernel: int = 4              # mamba_d_conv
    chunk: int = DEFAULT_CHUNK        # positions a chunk of the scan
    n_heads: int = 20
    n_kv_heads: int = 1
    d_ff: int = 8192                  # intermediate_size
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    rms_eps: float = 1e-6
    max_len: int = 262144
    dtype: Any = jnp.bfloat16         # what the mixers and the MLP compute in
    attention_impl: str = "dot"       # "dot" | "flash"
    ssm_impl: str = "xla"             # "xla" | "pallas": the scan and its convolution
    fused_head: bool = False          # pallas head + loss (ops/fused_xent)
    remat: bool = False               # jax.checkpoint around every layer

    def __post_init__(self):
        if self.attention_impl not in ("dot", "flash"):
            raise ValueError(f"Unknown attention_impl {self.attention_impl!r}; "
                             f"valid: 'dot', 'flash'")
        if self.ssm_impl not in SSM_IMPLS:
            raise ValueError(f"Unknown ssm_impl {self.ssm_impl!r}; "
                             f"valid: {SSM_IMPLS}")
        if self.n_heads % self.n_kv_heads or self.d_model % self.n_heads:
            raise ValueError("d_model must divide over n_heads, n_heads over "
                             "n_kv_heads")
        if not 0 <= self.attn_offset < self.attn_period:
            raise ValueError("attn_offset must lie in [0, attn_period)")

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def is_attention(self, layer: int) -> bool:
        """HF's ``layers_block_type`` rule."""
        return layer % self.attn_period == self.attn_offset

    @property
    def pattern(self) -> str:
        """``*`` an attention layer, ``M`` a Mamba-1 layer, in order."""
        return "".join("*" if self.is_attention(i) else "M"
                       for i in range(self.n_layers))


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)),
                            shape)


def _product(x, kernel, dtype):
    """``x @ kernel`` on ``dtype`` operands with the float32 accumulator as
    the result."""
    return jnp.dot(x.astype(dtype), kernel.astype(dtype),
                   preferred_element_type=jnp.float32)


def _precise_product(x, kernel):
    """``x @ kernel`` to float32's precision on the bfloat16 MXU: each operand
    is its bfloat16 head plus a bfloat16 tail of what the head leaves, and
    the product three one-pass products (the tails' own is under float32's
    rounding). ``reduce_precision`` and not a cast there and back, which XLA
    on the TPU drops."""
    head = lambda t: jax.lax.reduce_precision(t, 8, 7)  # noqa: E731
    x = x.astype(jnp.float32)
    x_head, k_head = head(x), head(kernel)
    dot = lambda a, b: _product(a, b, jnp.bfloat16)  # noqa: E731
    # the small terms first
    return dot(x_head, k_head) + (dot(x_head, kernel - k_head)
                                  + dot(x - x_head, k_head))


class PreciseDense(nn.Module):
    """``_dense`` on :func:`_precise_product`: float32 in, float32 out."""
    features: int

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", _INIT, (x.shape[-1], self.features),
                            jnp.float32)
        return _precise_product(x, kernel)


def _projection(features: int, dtype, name: str, precise: bool):
    """A bias-free projection's module: ``_dense`` in ``dtype``, or the same
    ``kernel`` under the same name through :func:`_precise_product`."""
    return (PreciseDense(features, name=name) if precise
            else _dense(features, dtype, name))


class GatedMLP(nn.Module):
    """``models/moe.py`` ``GatedMLP`` with a ``precise`` call."""
    width: int
    dtype: Any

    @nn.compact
    def __call__(self, h, precise: bool = False):
        gate = _projection(self.width, self.dtype, "gate", precise)(h)
        up = _projection(self.width, self.dtype, "up", precise)(h)
        return _projection(h.shape[-1], self.dtype, "down", precise)(
            nn.silu(gate) * up)


class Mamba1(nn.Module):
    """The state-space mixer: input projection to ``[x | z]``, the depthwise
    causal convolution with bias and SiLU, ``x W_x`` to ``[r | B | C]`` under
    Jamba's three norms, the step ``dt`` through the rank-``R`` bottleneck,
    the selective scan, the gate, the output projection. ``precise``: float32
    between the products and :func:`_precise_product` for each."""
    config: JambaConfig

    @nn.compact
    def __call__(self, h, precise: bool = False):
        cfg = self.config
        dtype = jnp.float32 if precise else cfg.dtype
        product = (_precise_product if precise else
                   lambda x, kernel: _product(x, kernel, dtype))
        e, n, r = cfg.d_inner, cfg.d_state, cfg.dt_rank
        taps = self.param("conv", _uniform(cfg.conv_kernel ** -0.5),
                          (e, cfg.conv_kernel), jnp.float32)
        conv_bias = self.param("conv_bias", _uniform(cfg.conv_kernel ** -0.5),
                               (e,), jnp.float32)
        x_proj = self.param("x_proj", _INIT, (e, r + 2 * n), jnp.float32)
        dt_proj = self.param("dt_proj", _uniform(r ** -0.5), (r, e), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init(cfg), (e,), jnp.float32)
        a_log = self.param("A_log", _a_log_init, (e, n), jnp.float32)
        d_skip = self.param("D", nn.initializers.ones, (e,), jnp.float32)
        # init runs the plain paths: shapes are all it needs
        impl = "xla" if self.is_initializing() else cfg.ssm_impl
        with jax.named_scope("ssm.in_proj"):
            x, z = jnp.split(_projection(2 * e, dtype, "in_proj", precise)(h),
                             2, axis=-1)
        with jax.named_scope("ssm.conv"):
            x = conv_silu(x, taps, conv_bias, impl).astype(dtype)
        with jax.named_scope("ssm.x_proj"):
            rbc = checkpoint_name(product(x, x_proj), KEPT_X_PROJ)
            rank, bmat, cmat = jnp.split(rbc, [r, r + n], axis=-1)
            rank = RMSNorm(cfg.rms_eps, dtype, name="dt_norm")(rank)
            bmat = RMSNorm(cfg.rms_eps, jnp.float32, name="b_norm")(bmat)
            cmat = RMSNorm(cfg.rms_eps, jnp.float32, name="c_norm")(cmat)
            dt = jax.nn.softplus(product(rank, dt_proj) + dt_bias)
        with jax.named_scope("ssm.scan"):
            y = selective_scan(x, dt, -jnp.exp(a_log), bmat, cmat, d_skip,
                               chunk=cfg.chunk, impl=impl)
        with jax.named_scope("ssm.out_proj"):
            gated = (y.astype(jnp.float32)
                     * jax.nn.silu(z.astype(jnp.float32))).astype(dtype)
            return _projection(cfg.d_model, dtype, "out_proj", precise)(gated)


class MultiQueryAttention(nn.Module):
    """Causal attention, ``H`` query heads over ``H_kv`` KV heads: no bias,
    no norm on q or k, no positional encoding, no gate."""
    config: JambaConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        b, length, _ = h.shape
        wide, narrow = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        q = checkpoint_name(_dense(wide, cfg.dtype, "query")(h), KEPT_QKV)
        k = checkpoint_name(_dense(narrow, cfg.dtype, "key")(h), KEPT_QKV)
        v = checkpoint_name(_dense(narrow, cfg.dtype, "value")(h), KEPT_QKV)
        if cfg.attention_impl == "flash" and not self.is_initializing():
            from autodist_tpu.ops.flash_attention import flash_attention
            # no position is turned into q or k: the projections' own rows go
            # in and the result's rows come out (``flash_attention``, "Where
            # the operands lie")
            ctx = flash_attention(q, k, v, causal=True,
                                  heads=(cfg.n_heads, cfg.n_kv_heads))
        else:
            heads = lambda t, n: t.reshape(b, length, n, cfg.head_dim)  # noqa: E731
            group = cfg.n_heads // cfg.n_kv_heads
            ctx = dot_product_attention(
                heads(q, cfg.n_heads),
                jnp.repeat(heads(k, cfg.n_kv_heads), group, axis=2),
                jnp.repeat(heads(v, cfg.n_kv_heads), group, axis=2),
                causal_mask(length, cfg.dtype), cfg.dtype)
        return _dense(cfg.d_model, cfg.dtype, "out")(ctx.reshape(b, length, wide))


class JambaBlock(nn.Module):
    """``h = x + mixer(RMSNorm(x)); h + MLP(RMSNorm(h))``; ``(x, zero)``: the
    stack's second output has nothing to carry here. ``precise``: the output
    takes the value of the layer computed a second time to float32's
    precision (attention goes through the flash kernels in ``dtype`` all the
    same) and keeps the ordinary layer's derivative."""
    config: JambaConfig
    attention: bool
    precise: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        input_norm = RMSNorm(cfg.rms_eps, jnp.float32, name="input_norm")
        ff_norm = RMSNorm(cfg.rms_eps, jnp.float32, name="ff_norm")
        mixer = (MultiQueryAttention(cfg, name="attn") if self.attention
                 else Mamba1(cfg, name="mamba"))
        mlp = GatedMLP(cfg.d_ff, cfg.dtype, name="mlp")

        def layer(x, precise):
            dtype = jnp.float32 if precise else cfg.dtype
            h = input_norm(x).astype(dtype)
            if self.attention:
                with jax.named_scope("jamba.attention"):
                    x = x + mixer(h)
            else:
                with jax.named_scope("jamba.mamba"):
                    x = x + mixer(h, precise)
            with jax.named_scope("jamba.mlp"):
                return x + mlp(ff_norm(x).astype(dtype), precise)

        x = constrain_batch(x)
        out = layer(x, False)
        if self.precise:
            out = out + jax.lax.stop_gradient(layer(x, True) - out)
        return constrain_batch(out), jnp.zeros((), jnp.float32)


class Jamba(Decoder):
    """``tokens [B, L] -> (logits or hidden, zero)``."""
    config: JambaConfig
    block = JambaBlock
    final_norm = "final_norm"
    tied = True
    kept = KEPT

    def layers(self):
        cfg = self.config
        telemetry.gauge("jamba.mamba_layers").set(cfg.pattern.count("M"))
        telemetry.gauge("jamba.attention_layers").set(cfg.pattern.count("*"))
        # in float32 every layer is exact already
        precise = PRECISE_LAYERS if cfg.dtype != jnp.float32 else 0
        return [(cfg.is_attention(i), i < precise)
                for i in range(cfg.n_layers)]
