"""EvaByte (``autodist_tpu/models/evabyte.py``) from its published config
file, cut to one chip's share of each layer's heads: builds the model, its
loss over the eight byte-ahead heads, the optimizer, the seeded host batch
pool and the required-operations counts (``benchmark/flops_evabyte.py``)."""

import numpy as np

from benchmark import flops_evabyte
from benchmark.families.common import Built

# What models/evabyte.py computes and no option of it changes: a
# configuration that says otherwise is another model.
COMPUTED = (("model_type", "evabyte"), ("attention_class", "eva"),
            ("hidden_act", "silu"), ("attention_bias", False),
            ("norm_add_unit_offset", True), ("fp32_logits", True),
            ("fp32_skip_add", True), ("rope_scaling", None),
            ("tie_word_embeddings", False))


def model_config(config: dict):
    import jax.numpy as jnp

    from autodist_tpu.models import evabyte
    assumed = config.get("assumed", {})
    for key, computed in COMPUTED:
        if config[key] != computed:
            raise ValueError(f"models/evabyte.py computes {key} = {computed!r}, "
                             f"the configuration says {config[key]!r}")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("EVA attention has a key head a query head")
    if config["hidden_size"] != config["layer_heads"] * config["head_dim"]:
        raise ValueError("hidden_size must be the layer's heads x head_dim")
    if assumed.get("fused_head"):
        raise ValueError("eight heads and float32 logits take the XLA head")
    return evabyte.EvaByteConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"], n_heads=config["layer_heads"],
        heads_held=config["num_attention_heads"],
        first_head_held=config["first_head_held"], head_dim=config["head_dim"],
        d_ff=config["intermediate_size"], window=config["window_size"],
        chunk=config["chunk_size"], n_pred_heads=config["num_pred_heads"],
        rope_theta=float(config["rope_theta"]), rms_eps=config["rms_norm_eps"],
        init_std=config["init_std"], max_len=config["max_seq_length"],
        dtype=jnp.dtype(assumed.get("activation_dtype", "bfloat16")),
        attention_impl=assumed.get("attention_impl", "dot"),
        remat=bool(assumed.get("remat", False)))


def batches(config: dict, n: int, sequences: int, seq_len: int, seed: int):
    """Bytes uniform over the vocabulary's rows: a byte stream has no Zipf
    law for the first steps to learn."""
    rng = np.random.default_rng(seed)
    return [{"tokens": rng.integers(0, config["vocab_size"],
                                    (sequences, seq_len + 1), dtype=np.int32)}
            for _ in range(n)]


def build(config: dict, traffic: dict, seed: int, global_batch: int,
          abstract: bool = False) -> Built:
    """``abstract=True`` gives the parameters as shapes (``jax.eval_shape``),
    for the compile rehearsal, which has no device to hold them."""
    import jax
    import optax

    from autodist_tpu.models import evabyte

    cfg = model_config(config)
    assumed = config.get("assumed", {})
    model = evabyte.EvaByte(cfg)
    init = lambda key: evabyte.init_params(cfg, rng=key)[1]  # noqa: E731
    key = jax.random.PRNGKey(seed)
    params = jax.eval_shape(init, key) if abstract else init(key)
    seq_len = traffic["seq_len"]
    rate = assumed.get("learning_rate", 1e-4)
    if assumed.get("warmup_steps"):
        rate = optax.linear_schedule(0.0, rate, assumed["warmup_steps"])
    return Built(
        params=params, loss_fn=evabyte.make_loss_fn(model),
        optimizer=getattr(optax, assumed.get("optimizer", "adamw"))(rate),
        pool=batches(config, traffic["pool_batches"], global_batch, seq_len,
                     seed),
        sample=batches(config, 1, traffic["check_sequences"], seq_len,
                       seed + 1)[0],
        tokens_per_step=global_batch * seq_len,
        train_flops_per_token=flops_evabyte.train_flops_per_token(
            config, seq_len),
        kernel_cost_per_step=(
            flops_evabyte.kernel_cost_per_step(config, traffic)
            if config.get("expects_pallas") else None),
        reference_config={
            "n_layers": cfg.n_layers, "head_dim": cfg.head_dim,
            "window": cfg.window, "chunk": cfg.chunk,
            "n_pred_heads": cfg.n_pred_heads, "rope_theta": cfg.rope_theta,
            "rms_eps": cfg.rms_eps})
