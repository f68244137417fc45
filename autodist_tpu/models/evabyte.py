"""EvaByte (``model_type`` ``evabyte``, 6.5B parameters) — a byte-level decoder
(vocabulary 320 = 256 bytes + 64 specials) whose attention is EVA (Zheng et
al., "Efficient Attention via Control Variates", ICLR 2023, in its
deterministic form): exact inside a window of 2,048 bytes, every earlier
window as pooled summaries of 16 keys, one softmax over both; and whose head
is eight heads, head ``j`` predicting the byte ``j + 1`` ahead. Of a layer's
attention heads this model may hold one chip's share.

The equations. ``config.json`` fixes the widths and the names ``eva``,
``window_size``, ``chunk_size``, ``num_pred_heads``; what goes beyond that is
the model's public code and the EVA paper as this repository's authors know
them, marked *assumed* in ``benchmark/configs/evabyte-6.5b.json``. ``L``
positions, width ``d``, ``H`` heads of ``D = 128``, ``s = D^-0.5``::

    x = E[tokens]                          float32 residual stream (fp32_skip_add)
    per layer:  x = x + Attn(norm1(x));  x = x + MLP(norm2(x))
    norm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)        w zeros at init (norm_add_unit_offset)
    MLP(h)  = W_down (silu(W_gate h) * (W_up h))         11,008 wide, no bias
    Attn(h): q, k, v = h W_q, h W_k, h W_v  to [L, H, D], no bias
      q, k = rope(q), rope(k)              the whole head width, rotate-half, theta 100,000
      window w(i) = i // 2,048; chunk c holds positions [16 c, 16 c + 16); a window is 128 chunks
      per head two learned vectors phi_h, mu_h [D]; a chunk's summary, from its 16 rotated keys
      and its values:
        a_cj = softmax_j(s * k_cj . phi_h);  k~_c = sum_j a_cj k_cj + mu_h;  v~_c = sum_j a_cj v_cj
      query i sees the positions j with w(j) = w(i), j <= i       at logits s * q_i . k_j
              and the chunks c < 128 w(i) (every earlier window's)  at logits s * q_i . k~_c
      ONE softmax over both; the output is the weighted sum of the v_j and the v~_c
      (window 0: plain causal attention);  o = concat_heads(...) W_o, no bias
    logits = norm_f(x) W_head              W_head [d, 8 x 320], float32 (fp32_logits), untied;
                                           the columns [320 j, 320 (j + 1)) are head j's
    loss = mean over the eight heads of each head's mean cross-entropy over its own valid
           positions: head j at position t against token t + 1 + j where the batch has one

**One chip's share of the heads** (``heads_held`` of ``n_heads`` from
``first_head_held`` on): the q, k and v projections are ``d x heads_held D``,
``phi`` and ``mu`` are the held heads', ``W_o`` is ``heads_held D x d``, and
what the other chips' heads would add to ``o`` — their partial sums, a
tensor-parallel layer's all-reduce — is left out, in the program and in the
reference alike; that partial result goes on to the MLP, which is whole (no
width is cut). No code stands in for the absent chips. With every head held
the layer is the published one.

The attention core is ``ops/eva_attention.py`` ``eva_attention``: two Pallas
kernels under ``attention_impl="kernel"``, the quadratic form under
``"dot"``. The stack, the loss (``n_pred_heads`` heads) and the init are
``models/decoder.py``'s.

Under ``remat`` every layer is a ``jax.checkpoint`` that keeps what EVA's
forward rule hands its backward (``o`` and the joint log-sum-exp: the core's
forward is the one thing a layer cannot make again from a matmul) and makes
the rest again from the residual stream, the pooled summaries with it (a pass
over k and v, 64 MB a layer). The MLP's ``gate`` and ``up`` products, 0.36 GB
each a layer at 16,384 positions, are made again: the benchmark's cell has no
room for them (PERF.md section 6, "PR 50").

Parameters and the residual stream are float32; the sublayers compute in
``dtype``.
"""

import dataclasses
import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from autodist_tpu import telemetry
from autodist_tpu.models.common import RMSNorm, rope
from autodist_tpu.models.decoder import Decoder, init_params, make_loss_fn  # noqa: F401
from autodist_tpu.models.moe import KEPT_GATE, KEPT_UP, GatedMLP, _dense
from autodist_tpu.models.transformer_lm import synthetic_batch  # noqa: F401 — re-exported
from autodist_tpu.ops.eva_attention import KEPT_NAME as KEPT_EVA, eva_attention, eva_pairs

# What a checkpointed layer keeps for its backward (module docstring)
KEPT = (KEPT_EVA, KEPT_GATE, KEPT_UP)


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    """Defaults are EvaByte's published sizes, every head held."""
    vocab_size: int = 320
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32                 # the layer's heads ...
    heads_held: int = 32              # ... those whose projections live here ...
    first_head_held: int = 0          # ... from this one on
    head_dim: int = 128
    d_ff: int = 11008
    window: int = 2048                # window_size
    chunk: int = 16                   # chunk_size
    n_pred_heads: int = 8             # num_pred_heads
    rope_theta: float = 1e5
    rms_eps: float = 1e-5
    init_std: float = 0.01275
    max_len: int = 32768
    dtype: Any = jnp.bfloat16         # what the sublayers compute in
    attention_impl: str = "dot"       # "dot" | "kernel"
    remat: bool = False               # jax.checkpoint around every layer, keeping KEPT

    fused_head = False                # [L, 8 x 320] float32 logits: the XLA head
    norm_unit_offset = True           # norm_add_unit_offset
    logits_dtype = jnp.float32        # fp32_logits

    def __post_init__(self):
        if self.attention_impl not in ("dot", "kernel"):
            raise ValueError(f"Unknown attention_impl {self.attention_impl!r}; "
                             f"valid: 'dot', 'kernel'")
        if self.window % self.chunk:
            raise ValueError(f"a window of {self.window} is not whole chunks "
                             f"of {self.chunk}")
        if not 0 < self.heads_held <= self.n_heads - self.first_head_held \
                or self.first_head_held < 0:
            raise ValueError(
                f"heads [{self.first_head_held}, {self.first_head_held} + "
                f"{self.heads_held}) are not among the layer's {self.n_heads}")

    @property
    def init(self):
        """Every matrix's initializer: ``normal(init_std)``."""
        return _normal(self.init_std)


@functools.lru_cache(maxsize=None)
def _normal(std: float):
    return nn.initializers.normal(std)


def eva_vector_init(key, shape, dtype=jnp.float32):
    """``phi`` and ``mu``: standard normal clipped to ±1, times ``D^-0.5``."""
    return (jnp.clip(jax.random.normal(key, shape, dtype), -1.0, 1.0)
            * shape[-1] ** -0.5)


class EvaAttention(nn.Module):
    """EVA attention over the heads held here (module docstring): the
    projections of ``heads_held`` heads, their ``phi`` and ``mu``, the core,
    and the held heads' part of the output projection."""
    config: EvaByteConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        b, length, _ = h.shape
        held, d = cfg.heads_held, cfg.head_dim
        q, k, v = (_dense(held * d, cfg.dtype, name, cfg.init)(h).reshape(
            b, length, held, d) for name in ("query", "key", "value"))
        phi, mu = (self.param(name, eva_vector_init, (held, d), jnp.float32)
                   for name in ("phi", "mu"))
        out = _dense(cfg.d_model, cfg.dtype, "out", cfg.init)
        if self.is_initializing():
            # Shapes are all that init needs: eight positions are no window.
            return out(v.reshape(b, length, held * d))
        telemetry.gauge("attention.heads_held").set(held)
        visible, computed = eva_pairs(length, cfg.window, cfg.chunk)
        calls = b * held * cfg.n_layers    # set, not added: a layer is traced more than once
        telemetry.gauge("eva.pairs.visible").set(calls * visible)
        telemetry.gauge("eva.pairs.computed").set(calls * computed)
        with jax.named_scope("attn.rope"):
            positions = jnp.arange(length)
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        ctx = eva_attention(q, k, v, phi, mu, window=cfg.window, chunk=cfg.chunk,
                            impl=cfg.attention_impl)
        return out(ctx.reshape(b, length, held * d))


class EvaByteBlock(nn.Module):
    """``x + Attn(norm(x))``, then ``+ MLP(norm(.))``; ``(x, zero)``: the
    stack's second output is the mixture families'."""
    config: EvaByteConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        x = x + EvaAttention(cfg, name="attn")(
            RMSNorm(cfg.rms_eps, cfg.dtype, True, name="ln_attn")(x))
        m = GatedMLP(cfg.d_ff, cfg.dtype, cfg.init, name="mlp")(
            RMSNorm(cfg.rms_eps, cfg.dtype, True, name="ln_mlp")(x))
        return x + m, jnp.zeros((), jnp.float32)


class EvaByte(Decoder):
    """``tokens [B, L] -> (logits [B, L, n_pred_heads * vocab_size] float32 or
    hidden, zero)``."""
    config: EvaByteConfig
    block = EvaByteBlock
    kept = KEPT
