"""Plain float32 reference of the BERT encoder's masked-LM loss, written
from ``autodist_tpu/models/bert.py``'s equations: token + type + position
embeddings, pre-LN encoder blocks with biases, padding mask on token 0,
final LayerNorm, prediction slots gathered before the tied head, weighted
mean cross-entropy. No kernels, no bfloat16, no flax. Follows the model
file; its departures from BERT as published are in the configuration file."""

import jax.numpy as jnp

from benchmark.reference.common import (attention, gelu_tanh, layer_norm,
                                        mean_nll, scan_blocks)


def _block(p, x, mask):
    h = layer_norm(x, p["ln_attn"])
    q, k, v = (jnp.einsum("bld,dhk->blhk", h, p[name]["kernel"])
               + p[name]["bias"] for name in ("query", "key", "value"))
    ctx = attention(q, k, v, mask)
    x = x + jnp.einsum("blhk,hkd->bld", ctx, p["out"]["kernel"]) \
        + p["out"]["bias"]
    h = layer_norm(x, p["ln_mlp"])
    h = gelu_tanh(h @ p["mlp_in"]["kernel"] + p["mlp_in"]["bias"])
    return x + h @ p["mlp_out"]["kernel"] + p["mlp_out"]["bias"]


def loss(params, batch, *, n_heads: int, n_layers: int):
    del n_heads
    tokens = batch["tokens"]
    length = tokens.shape[1]
    table = params["embed"]["embedding"]
    x = (table[tokens] + params["type_embed"]["embedding"][batch["token_types"]]
         + params["pos_embed"][:length][None])
    mask = jnp.where(tokens == 0, -1e9, 0.0).astype(jnp.float32)[:, None, None, :]
    x = scan_blocks(_block, [params[f"layer_{i}"] for i in range(n_layers)],
                    x, mask)
    x = layer_norm(x, params["ln_f"])
    x = jnp.take_along_axis(x, batch["mlm_positions"][..., None], axis=1)
    return mean_nll(x @ table.T, batch["mlm_targets"], batch["mlm_weights"])
