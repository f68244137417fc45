"""BERT encoder with the masked-LM loss (``autodist_tpu/models/bert.py``)
from a BERT-style config file."""

import numpy as np

from benchmark import flops
from benchmark.families.common import Built, optimizer, zipf_tokens


def model_config(config: dict):
    import jax.numpy as jnp

    from autodist_tpu.models import bert
    assumed = config.get("assumed", {})
    return bert.BertConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"],
        max_len=config["max_position_embeddings"],
        type_vocab=config["type_vocab_size"],
        dtype=jnp.dtype(assumed.get("activation_dtype", "bfloat16")))


def batches(config: dict, n: int, sequences: int, seq_len: int,
            predictions: int, seed: int):
    """Token 0 is padding in the model's mask, so ids start at 1; the
    prediction slots of a sequence are distinct positions, as a masking
    pass over real text gives."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        positions = np.argsort(rng.random((sequences, seq_len)),
                               axis=1)[:, :predictions].astype(np.int32)
        out.append({
            "tokens": zipf_tokens(rng, (sequences, seq_len),
                                  config["vocab_size"], low=1),
            "token_types": np.zeros((sequences, seq_len), np.int32),
            "mlm_positions": positions,
            "mlm_targets": zipf_tokens(rng, (sequences, predictions),
                                       config["vocab_size"], low=1),
            "mlm_weights": np.ones((sequences, predictions), np.float32)})
    return out


def build(config: dict, traffic: dict, seed: int, global_batch: int,
          abstract: bool = False) -> Built:
    """``abstract=True`` gives the parameters as shapes (``jax.eval_shape``),
    for the compile rehearsal, which has no device to hold them."""
    import jax
    import jax.numpy as jnp

    from autodist_tpu.models import bert
    from autodist_tpu.models.common import jit_init

    cfg = model_config(config)
    model = bert.Bert(cfg)
    seq_len, predictions = traffic["seq_len"], traffic["predictions"]
    shape = jnp.zeros((2, seq_len), jnp.int32)
    init = lambda key: jit_init(model, shape, shape, rng=key)  # noqa: E731
    key = jax.random.PRNGKey(seed)
    params = jax.eval_shape(init, key) if abstract else init(key)
    return Built(
        params=params, loss_fn=bert.make_mlm_loss_fn(model),
        optimizer=optimizer(config, "adamw"),
        pool=batches(config, traffic["pool_batches"], global_batch, seq_len,
                     predictions, seed),
        sample=batches(config, 1, traffic["check_sequences"], seq_len,
                       predictions, seed + 1)[0],
        tokens_per_step=global_batch * seq_len,
        train_flops_per_token=flops.train_flops_per_token(
            d_model=cfg.d_model, n_layers=cfg.n_layers, d_ff=cfg.d_ff,
            vocab_size=cfg.vocab_size, seq_len=seq_len, causal=False,
            predicted_fraction=predictions / seq_len),
        kernel_cost_per_step=None,
        reference_config={"n_heads": cfg.n_heads, "n_layers": cfg.n_layers})
