"""Shared layers and loss helpers for the model zoo."""

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from autodist_tpu import telemetry


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * scale`` over the last axis, no bias and
    no mean subtraction; the arithmetic is float32 whatever comes in, the
    result is cast to ``dtype`` (float32 for a reader that must not see the
    activation dtype's rounding, an MoE router for one). ``unit_offset``:
    the weight is ``1 + scale`` and ``scale`` starts at zero (EvaByte's
    ``norm_add_unit_offset``), so weight decay pulls the weight to one."""
    eps: float = 1e-5
    dtype: Any = jnp.float32
    unit_offset: bool = False

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.zeros if self.unit_offset else nn.initializers.ones
        scale = self.param("scale", init, (x.shape[-1],), jnp.float32)
        if self.unit_offset:
            scale = 1.0 + scale
        x = x.astype(jnp.float32)
        rms = jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                            + self.eps)
        return (x * rms * scale).astype(self.dtype)


def rope(x, positions, theta: float = 10000.0,
         rotary_dim: Optional[int] = None):
    """Rotary position embedding, rotate-half form over the first
    ``rotary_dim`` columns of the head dim (None: the whole of it).

    x: ``[..., L, H, D]``; positions: ``[L]``. With ``R = rotary_dim`` (even),
    the pair ``(x[i], x[i + R/2])``, ``i < R/2``, is rotated by ``position *
    theta^(-2i/R)`` and the columns from ``R`` on pass as they are (a partial
    rotary factor: MiMo-V2 turns 64 of 192); the rotation is computed in
    float32 and cast back to ``x.dtype``."""
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        if rotary_dim % 2:
            raise ValueError(f"rotary_dim {rotary_dim} must be even")
        return jnp.concatenate(
            [rope(x[..., :rotary_dim], positions, theta), x[..., rotary_dim:]],
            axis=-1)
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : d // 2], x32[..., d // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x32 * cos + rotated * sin).astype(x.dtype)


def rope_pairs(x, positions, theta: float = 10000.0,
               rotary_dim: Optional[int] = None):
    """Rotary position embedding in the interleaved-pair form, over the last
    ``rotary_dim`` columns of the head dim (None: all of it).

    x: ``[..., L, H, D]``; positions: ``[L]``. With ``first = D -
    rotary_dim``, the pair ``(x[first + 2i], x[first + 2i + 1])`` is rotated
    by ``position * theta^(-2i/rotary_dim)`` and the columns before ``first``
    pass as they are; the columns keep their order. It is :func:`rope` under
    the permutation that puts the even columns before the odd ones (what
    DeepSeek-V3's ``rope_interleave`` does to activations before it rotates
    halves); a pair's partner is the lane beside it. float32 inside, cast
    back to ``x.dtype``. Only the rotary columns are read into float32 and
    turned; the others are passed on as they came (until PR 41 they went
    through ``x * 1 + partner * 0`` in float32 with the rest, and the shift
    by one lane, which XLA on TPU answers by laying the whole array out
    anew and writing both shifted copies, was over all ``D`` columns: three
    times the bytes at latent attention's 64 of 192)."""
    d = x.shape[-1]
    r = d if rotary_dim is None else rotary_dim
    if r % 2 or (d - r) % 2:
        raise ValueError(f"rotary_dim {r} of {d} columns: both parts must be even")
    if r < d:
        return jnp.concatenate(
            [x[..., :d - r], rope_pairs(x[..., d - r:], positions, theta)],
            axis=-1)
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.repeat(jnp.cos(angles), 2, axis=-1)[:, None, :]
    sin = jnp.repeat(jnp.sin(angles), 2, axis=-1)[:, None, :]
    x32 = x.astype(jnp.float32)
    partner = jnp.where(jnp.arange(d) % 2 == 0, -jnp.roll(x32, -1, axis=-1),
                        jnp.roll(x32, 1, axis=-1))
    return (x32 * cos + partner * sin).astype(x.dtype)


def head_columns(heads: int, d: int, dtype=jnp.float32):
    """``[heads, heads d]`` of 0 / 1: row ``h`` marks head ``h``'s ``d``
    columns of ``[..., heads d]`` rows. A product with it sums a head's
    columns or spreads a head's value over them without laying the rows out
    as ``[..., heads, d]`` (on TPU other bytes: PERF.md section 6, "PR 41")."""
    return jnp.repeat(jnp.eye(heads, dtype=dtype), d, axis=1)


def keeping(names):
    """The ``jax.checkpoint`` policy that keeps the values named in ``names``
    and nothing else, and books what it keeps as it decides (when a
    checkpointed layer is differentiated, at trace time): gauges
    ``remat.kept_values`` and ``remat.kept_bytes``, of this call's layers
    together."""
    keep = jax.checkpoint_policies.save_only_these_names(*names)
    kept = [0, 0]

    def policy(prim, *avals, **params):
        if not keep(prim, *avals, **params):
            return False
        kept[0] += 1
        kept[1] += sum(a.size * a.dtype.itemsize for a in avals)
        telemetry.gauge("remat.kept_values").set(kept[0])
        telemetry.gauge("remat.kept_bytes").set(kept[1])
        return True

    return policy


def jit_init(model, *args, rng: Optional[jax.Array] = None):
    """``model.init`` under jit, returning the params tree.

    One compiled program instead of eager op-by-op dispatch (deep-CNN
    initialization such as DenseNet-121 is hundreds of eager ops otherwise).
    The single place all model zoo init paths go through."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    return jax.jit(model.init)(rng, *args)["params"]


def num_groups(channels: int, max_groups: int) -> int:
    """Largest GroupNorm group count <= max_groups that divides the channel count
    (CNN widths like 80/48/76 are not multiples of the usual 32)."""
    g = min(max_groups, channels)
    while channels % g:
        g -= 1
    return g


def sample_logits(logits, key, temperature: float = 0.0, top_k: int = 0,
                  top_p: float = 0.0):
    """One sampling step over ``[B, vocab]`` logits -> ``[B]`` int32 tokens.

    ``temperature=0`` is greedy argmax (``key`` unused); otherwise logits are
    scaled by ``1/temperature``, then optionally truncated to the ``top_k``
    best and/or the nucleus of smallest-count tokens whose probability mass
    reaches ``top_p`` (0 < p <= 1; the first token past the threshold is
    kept, so the nucleus always covers >= p and is never empty). Both filters
    compose (k first, then p over the survivors). f32 throughout — bf16
    logit gaps near the distribution tail would quantize away."""
    logits = logits.astype(jnp.float32)
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p > 0.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]   # descending
        cum = jnp.cumsum(jax.nn.softmax(sorted_logits, axis=-1), axis=-1)
        # Keep ranks whose PRECEDING mass is < p (shift by one): the token
        # crossing the threshold stays in the nucleus.
        keep = jnp.concatenate(
            [jnp.ones_like(cum[..., :1], bool), cum[..., :-1] < top_p],
            axis=-1)
        # Smallest kept logit per row = the nucleus cutoff.
        cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1,
                         keepdims=True)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def lm_head_logits(h, params, tied: bool = False):
    """``[..., D] hidden -> [..., V] logits`` through the zoo's LM-head param
    contract (same table/layout rule as :func:`fused_lm_head_nll`; same
    compute-dtype convention as the flax head: table cast to the activation
    dtype). Lets callers project a SLICE of positions — e.g. generation's
    prefill needs only the last position's logits, not a [B, P, V] tensor."""
    if tied:
        table = params["embed"]["embedding"]          # [V, D]
        return h @ table.astype(h.dtype).T
    return h @ params["lm_head"]["kernel"].astype(h.dtype)  # [D, V]


def fused_lm_head_nll(h, params, targets, tied: bool = False):
    """Per-token NLL [B, T] through the fused pallas head+loss for the zoo's
    flax LM-head convention — THE single definition of which param is the head
    table and in which layout (untied: ``params['lm_head']['kernel']``, [D, V];
    tied: ``params['embed']['embedding']``, [V, D]) so no model's fused loss
    can drift from another's."""
    from autodist_tpu.ops.fused_xent import fused_softmax_xent
    h2 = h.reshape(-1, h.shape[-1])
    if tied:
        nll = fused_softmax_xent(h2, params["embed"]["embedding"],
                                 targets.reshape(-1), w_layout="vd")
    else:
        nll = fused_softmax_xent(h2, params["lm_head"]["kernel"],
                                 targets.reshape(-1))
    return nll.reshape(targets.shape)


def make_classification_loss_fn(model) -> Callable:
    """Softmax cross entropy over {"images", "labels"} batches (ResNet/VGG style)."""

    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch["images"])
        logprobs = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logprobs, batch["labels"][:, None], axis=-1)[:, 0]
        return nll.mean()

    return loss_fn
