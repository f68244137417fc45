"""OLMoE (``autodist_tpu/models/olmoe.py``) from its published config file:
builds the model, its loss, the seeded host batch pool and the
required-operations counts."""

from benchmark import flops, flops_moe
from benchmark.families.common import Built, optimizer
from benchmark.families.transformer_lm import batches   # the same LM batches


def model_config(config: dict):
    import jax.numpy as jnp

    from autodist_tpu.models import olmoe
    assumed = config.get("assumed", {})
    for key, published in (("hidden_act", "silu"), ("norm_topk_prob", False),
                           ("attention_bias", False), ("clip_qkv", None),
                           ("rope_scaling", None),
                           ("tie_word_embeddings", False)):
        if config[key] != published:
            raise ValueError(f"models/olmoe.py computes {key} = {published!r}, "
                             f"the configuration says {config[key]!r}")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("models/olmoe.py has no grouped KV heads")
    return olmoe.OlmoeConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        d_expert=config["intermediate_size"], n_experts=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        max_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(assumed.get("activation_dtype", "bfloat16")),
        attention_impl=assumed.get("attention_impl", "dot"),
        fused_head=bool(assumed.get("fused_head", False)),
        load_balance_weight=assumed["load_balance_weight"],
        router_z_weight=assumed["router_z_weight"])


def build(config: dict, traffic: dict, seed: int, global_batch: int,
          abstract: bool = False) -> Built:
    """``abstract=True`` gives the parameters as shapes (``jax.eval_shape``),
    for the compile rehearsal, which has no device to hold them."""
    import jax

    from autodist_tpu.models import olmoe

    cfg = model_config(config)
    init = lambda key: olmoe.init_params(cfg, rng=key)[1]  # noqa: E731
    key = jax.random.PRNGKey(seed)
    params = jax.eval_shape(init, key) if abstract else init(key)
    seq_len = traffic["seq_len"]
    kernel_cost = None
    if config.get("expects_pallas"):
        calls = traffic["accumulation"]
        micro = global_batch // calls          # sequences per kernel call, all chips
        kernel_cost = (
            (flops.flash_attention_cost(
                batch=micro, seq_len=seq_len, n_heads=cfg.n_heads,
                head_dim=cfg.d_model // cfg.n_heads, causal=True)
             + flops_moe.gmm_cost(
                 rows=micro * seq_len * cfg.top_k, d_model=cfg.d_model,
                 d_expert=cfg.d_expert, n_experts=cfg.n_experts))
            * cfg.n_layers
            + flops.fused_xent_cost(rows=micro * seq_len, d_model=cfg.d_model,
                                    vocab_size=cfg.vocab_size)) * calls
    return Built(
        params=params, loss_fn=olmoe.make_loss_fn(olmoe.Olmoe(cfg)),
        optimizer=optimizer(config, "adamw"),
        pool=batches(config, traffic["pool_batches"], global_batch, seq_len,
                     seed),
        sample=batches(config, 1, traffic["check_sequences"], seq_len,
                       seed + 1)[0],
        tokens_per_step=global_batch * seq_len,
        train_flops_per_token=flops_moe.train_flops_per_token(
            d_model=cfg.d_model, n_layers=cfg.n_layers, d_expert=cfg.d_expert,
            n_experts=cfg.n_experts, top_k=cfg.top_k,
            vocab_size=cfg.vocab_size, seq_len=seq_len),
        kernel_cost_per_step=kernel_cost,
        reference_config={
            "n_heads": cfg.n_heads, "n_layers": cfg.n_layers,
            "top_k": cfg.top_k, "rms_eps": cfg.rms_eps,
            "rope_theta": cfg.rope_theta,
            "load_balance_weight": cfg.load_balance_weight,
            "router_z_weight": cfg.router_z_weight})
