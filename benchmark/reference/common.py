"""The few equations both plain references share. float32 throughout;
the caller traces them under ``jax.default_matmul_precision("highest")``."""

import jax
import jax.numpy as jnp

LN_EPS = 1e-6   # flax.linen.LayerNorm's default, which the model files use


def layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def attention(q, k, v, additive_mask):
    """Softmax attention over [B, L, H, Dh]; ``additive_mask`` broadcasts to
    [B, H, Lq, Lk]."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    probs = jax.nn.softmax(scores + additive_mask, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def mean_nll(logits, targets, weights=None):
    nll = (jax.nn.logsumexp(logits, axis=-1)
           - jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0])
    if weights is None:
        return jnp.mean(nll)
    return jnp.sum(nll * weights) / jnp.maximum(jnp.sum(weights), 1.0)


def scan_blocks(block, layers, x, *consts):
    """``x`` through ``block(params_i, x, *consts)`` for each layer's
    parameters in turn. The layers are stacked and scanned, each under
    ``jax.checkpoint``: the same arithmetic as a Python loop, in a program a
    twenty-fourth the size (set-up pays for compiling and loading it in every
    run) that holds one block's score planes at a time in its backward pass.
    """
    stacked = jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *layers)

    def body(carry, params):
        return jax.checkpoint(block)(params, carry, *consts), None

    out, _ = jax.lax.scan(body, x, stacked)
    return out
