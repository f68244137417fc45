"""Decoder-only Transformer LM (``autodist_tpu/models/transformer_lm.py``)
from a GPT-2-style config file: builds the model, its loss, the seeded host
batch pool and the required-operations counts."""

import numpy as np

from benchmark import flops
from benchmark.families.common import Built, optimizer, zipf_tokens


def model_config(config: dict):
    import jax.numpy as jnp

    from autodist_tpu.models import transformer_lm
    assumed = config.get("assumed", {})
    return transformer_lm.TransformerLMConfig(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], n_layers=config["n_layer"],
        d_ff=config["n_inner"], max_len=config["n_positions"],
        dtype=jnp.dtype(assumed.get("activation_dtype", "bfloat16")),
        attention_impl=assumed.get("attention_impl", "dot"),
        remat=bool(assumed.get("remat", False)),
        fused_head=bool(assumed.get("fused_head", False)),
        tied_output=bool(config.get("tie_word_embeddings", True)))


def batches(config: dict, n: int, sequences: int, seq_len: int, seed: int):
    rng = np.random.default_rng(seed)
    return [{"tokens": zipf_tokens(rng, (sequences, seq_len + 1),
                                   config["vocab_size"])} for _ in range(n)]


def build(config: dict, traffic: dict, seed: int, global_batch: int,
          abstract: bool = False) -> Built:
    """``abstract=True`` gives the parameters as shapes (``jax.eval_shape``),
    for the compile rehearsal, which has no device to hold them."""
    import jax

    from autodist_tpu.models import transformer_lm

    cfg = model_config(config)
    init = lambda key: transformer_lm.init_params(cfg, rng=key)[1]  # noqa: E731
    key = jax.random.PRNGKey(seed)
    model = transformer_lm.TransformerLM(cfg)
    params = jax.eval_shape(init, key) if abstract else init(key)
    seq_len = traffic["seq_len"]
    kernel_cost = None
    if config.get("expects_pallas"):
        calls = traffic["accumulation"]
        micro = global_batch // calls          # sequences per kernel call, all chips
        kernel_cost = (flops.flash_attention_cost(
            batch=micro, seq_len=seq_len, n_heads=cfg.n_heads,
            head_dim=cfg.d_model // cfg.n_heads, causal=True) * cfg.n_layers
            + flops.fused_xent_cost(rows=micro * seq_len, d_model=cfg.d_model,
                                    vocab_size=cfg.vocab_size)) * calls
    return Built(
        params=params, loss_fn=transformer_lm.make_loss_fn(model),
        optimizer=optimizer(config, "adam"),
        pool=batches(config, traffic["pool_batches"], global_batch, seq_len,
                     seed),
        sample=batches(config, 1, traffic["check_sequences"], seq_len,
                       seed + 1)[0],
        tokens_per_step=global_batch * seq_len,
        train_flops_per_token=flops.train_flops_per_token(
            d_model=cfg.d_model, n_layers=cfg.n_layers, d_ff=cfg.d_ff,
            vocab_size=cfg.vocab_size, seq_len=seq_len, causal=True),
        kernel_cost_per_step=kernel_cost,
        reference_config={"n_heads": cfg.n_heads, "n_layers": cfg.n_layers,
                          "tied": cfg.tied_output})
