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
import functools
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from autodist_tpu.models.common import RMSNorm, rope
from autodist_tpu.models.moe import (  # noqa: F401 — the mixture's, under this family's names
    GatedMLP, _dense, _INIT, balance_expert_bias,
    balanced_optimizer as make_optimizer, expert_loads, sigmoid_routed_share,
    sigmoid_topk_route, sown_loads)
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
        if not 0 <= self.n_dense_layers <= len(self.layer_types):
            raise ValueError("n_dense_layers must be in [0, n_layers]")
        if not 1 <= self.top_k <= self.n_experts_routed:
            raise ValueError("top_k must be in [1, n_experts_routed]")
        if not (0 <= self.first_expert_held and self.experts_held >= 1
                and self.first_expert_held + self.experts_held
                <= self.n_experts_routed):
            raise ValueError("the experts held must lie inside the router's width")

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


class SharedAndRoutedExperts(nn.Module):
    """The expert layer's MLP: a shared expert every token passes, beside this
    chip's share of the sigmoid top-k routed experts
    (``models/moe.py`` :func:`sigmoid_routed_share`, whose parameters live in
    this module's scope). ``__call__(h)`` takes the float32 normalised input
    ``[B, S, d]`` and returns ``(m float32, the bias term of the loss)``."""
    config: AfmoeConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        with jax.named_scope("moe.shared"):
            shared = GatedMLP(cfg.d_expert * cfg.n_shared_experts, cfg.dtype,
                              name="shared")(h.astype(cfg.dtype))
        y, bias_term = sigmoid_routed_share(
            self, h, router_width=cfg.n_experts_routed,
            experts_held=cfg.experts_held,
            first_expert_held=cfg.first_expert_held, top_k=cfg.top_k,
            d_expert=cfg.d_expert, rows_bound=cfg.rows_bound,
            route=functools.partial(sigmoid_topk_route,
                                    route_norm=cfg.route_norm,
                                    route_scale=cfg.route_scale),
            dtype=cfg.dtype)
        return shared.astype(jnp.float32) + y, bias_term


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
            m, bias_term = SharedAndRoutedExperts(cfg, name="moe")(h)
        return x + norm("ln_post_mlp", jnp.float32)(m), bias_term


class Afmoe(nn.Module):
    """``tokens [B, L] -> (logits or hidden, bias term)``; the bias term is
    the sum over the expert layers of the zero-valued term whose gradient is
    the load error (module docstring)."""
    config: AfmoeConfig

    @nn.compact
    def __call__(self, tokens, return_hidden: bool = False):
        cfg = self.config
        x = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=jnp.float32,
                     param_dtype=jnp.float32, embedding_init=_INIT,
                     name="embed")(tokens)
        if cfg.mup_enabled:
            x = x * np.float32(cfg.d_model ** 0.5)
        bias_term = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(cfg.layer_types):
            x, term = AfmoeBlock(cfg, kind, i < cfg.n_dense_layers,
                                 name=f"block_{i}")(x)
            bias_term = bias_term + term
        x = RMSNorm(cfg.rms_eps, cfg.dtype, name="ln_f")(x)
        if return_hidden:
            # The fused-head loss owns the projection; the head's parameters
            # exist from init, which runs the path below.
            return x, bias_term
        return _dense(cfg.vocab_size, cfg.dtype, "lm_head")(x), bias_term


def make_loss_fn(model: Afmoe) -> Callable:
    """Mean next-token cross-entropy (+ the expert layers' bias terms, zero in
    value: module docstring); batch = ``{"tokens": int32 [B, L+1]}``."""
    cfg = model.config

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        if cfg.fused_head:
            from autodist_tpu.models.common import fused_lm_head_nll
            h, bias_term = model.apply({"params": params}, inputs,
                                       return_hidden=True)
            nll = fused_lm_head_nll(h, params, targets)
        else:
            logits, bias_term = model.apply({"params": params}, inputs)
            logprobs = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(logprobs, targets[..., None],
                                       axis=-1)[..., 0]
        return nll.mean() + bias_term

    return loss_fn


def init_params(config: AfmoeConfig, rng: Optional[jax.Array] = None,
                batch_size: int = 2):
    from autodist_tpu.models.common import jit_init
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    model = Afmoe(config)
    tokens = jnp.zeros((batch_size, min(8, config.max_len)), jnp.int32)
    return model, jit_init(model, tokens, rng=rng)
