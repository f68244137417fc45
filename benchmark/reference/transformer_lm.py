"""Plain float32 reference of the decoder-only Transformer LM's loss,
written from ``autodist_tpu/models/transformer_lm.py``'s equations: pre-LN
blocks, learned positions, tanh GELU, no projection biases, tied or untied
head, mean next-token cross-entropy. No kernels, no bfloat16, no flax: the
parameter tree is read by name. The blocks are scanned under
``jax.checkpoint`` (``reference/common.py``), which changes the program's
size and memory, not its arithmetic.

Departures of the model file from GPT-2 as published are listed in the
configuration file (``departures``); this reference follows the model file,
because it checks the system, not the paper."""

import jax.numpy as jnp

from benchmark.reference.common import (attention, gelu_tanh, layer_norm,
                                        mean_nll, scan_blocks)


def _block(p, x, mask):
    h = layer_norm(x, p["ln_attn"])
    q, k, v = (jnp.einsum("bld,dhk->blhk", h, p["attn"][name]["kernel"])
               for name in ("query", "key", "value"))
    ctx = attention(q, k, v, mask)
    x = x + jnp.einsum("blhk,hkd->bld", ctx, p["attn"]["out"]["kernel"])
    h = layer_norm(x, p["ln_mlp"])
    h = gelu_tanh(h @ p["mlp_in"]["kernel"])
    return x + h @ p["mlp_out"]["kernel"]


def loss(params, batch, *, n_heads: int, n_layers: int, tied: bool):
    del n_heads   # the kernels' shapes carry it
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    length = inputs.shape[1]
    table = params["embed"]["embedding"]
    x = table[inputs] + params["pos_embed"][:length][None]
    causal = jnp.tril(jnp.ones((length, length), bool))
    mask = jnp.where(causal, 0.0, -1e9).astype(jnp.float32)
    x = scan_blocks(_block, [params[f"block_{i}"] for i in range(n_layers)],
                    x, mask)
    x = layer_norm(x, params["ln_f"])
    logits = x @ table.T if tied else x @ params["lm_head"]["kernel"]
    return mean_nll(logits, targets)
