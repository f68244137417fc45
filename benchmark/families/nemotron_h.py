"""Nemotron-H (``autodist_tpu/models/nemotron_h.py``) from its published
config file, cut to one chip's share: builds the model, its loss, the
optimizer that carries the expert-bias rule, the seeded host batch pool and
the required-operations counts (``benchmark/flops_nemotron_h.py``)."""

from benchmark import flops_nemotron_h
from benchmark.families.common import Built
from benchmark.families.transformer_lm import batches   # the same LM batches

# What models/nemotron_h.py computes and no option of it changes: a
# configuration that says otherwise is another model.
COMPUTED = (("model_type", "nemotron_h"), ("mlp_hidden_act", "relu2"),
            ("mamba_hidden_act", "silu"), ("use_conv_bias", True),
            ("mamba_proj_bias", False), ("attention_bias", False),
            ("mlp_bias", False), ("use_bias", False),
            ("tie_word_embeddings", False), ("n_shared_experts", 1),
            ("n_group", 1), ("topk_group", 1), ("sliding_window", None))


def model_config(config: dict):
    import jax.numpy as jnp

    from autodist_tpu.models import nemotron_h
    assumed = config.get("assumed", {})
    for key, computed in COMPUTED:
        if config[key] != computed:
            raise ValueError(f"models/nemotron_h.py computes {key} = "
                             f"{computed!r}, the configuration says "
                             f"{config[key]!r}")
    if len(config["hybrid_override_pattern"]) != config["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern must name num_hidden_layers "
                         "layers")
    if config["layer_norm_epsilon"] != config["norm_eps"]:
        raise ValueError("models/nemotron_h.py has one epsilon for its norms")
    return nemotron_h.NemotronHConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        pattern=config["hybrid_override_pattern"],
        mamba_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"], n_groups=config["n_groups"],
        d_state=config["ssm_state_size"], conv_kernel=config["conv_kernel"],
        chunk=config["chunk_size"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["moe_shared_expert_intermediate_size"],
        n_experts_routed=config["router_width"],
        experts_held=config["n_routed_experts"],
        first_expert_held=config["first_expert_held"],
        top_k=config["num_experts_per_tok"], rows_bound=assumed.get("rows_bound"),
        route_norm=config["norm_topk_prob"],
        route_scale=float(config["routed_scaling_factor"]),
        route_eps=assumed.get("route_eps", 1e-20),
        load_balance_coeff=assumed.get("load_balance_coeff", 1e-3),
        time_step_min=config["time_step_min"],
        time_step_max=config["time_step_max"],
        time_step_floor=config["time_step_floor"],
        rescale_prenorm_residual=config["rescale_prenorm_residual"],
        rms_eps=config["norm_eps"], max_len=config["max_position_embeddings"],
        dtype=jnp.dtype(assumed.get("activation_dtype", "bfloat16")),
        attention_impl=assumed.get("attention_impl", "dot"),
        ssm_impl=assumed.get("ssm_impl", "xla"),
        fused_head=bool(assumed.get("fused_head", False)),
        remat=bool(assumed.get("remat", False)),
        exact_first_layer=bool(assumed.get("exact_first_layer", False)))


def build(config: dict, traffic: dict, seed: int, global_batch: int,
          abstract: bool = False) -> Built:
    """``abstract=True`` gives the parameters as shapes (``jax.eval_shape``),
    for the compile rehearsal, which has no device to hold them."""
    import jax
    import numpy as np
    import optax

    from autodist_tpu.models import nemotron_h

    cfg = model_config(config)
    assumed = config.get("assumed", {})
    model = nemotron_h.NemotronH(cfg)
    init = lambda key: nemotron_h.init_params(cfg, rng=key)[1]  # noqa: E731
    key = jax.random.PRNGKey(seed)
    params = jax.eval_shape(init, key) if abstract else init(key)
    seq_len = traffic["seq_len"]
    pool = batches(config, traffic["pool_batches"], global_batch, seq_len, seed)
    balance = assumed.get("expert_bias_balance")
    if balance and not abstract:
        # a trained router's loads, not a random one's: the balancing rule
        # alone on the seeded pool, its coefficient falling to the trained one
        params = nemotron_h.balance_expert_bias(
            model, params, [jax.numpy.asarray(b["tokens"][:, :-1]) for b in pool],
            np.geomspace(balance["first_coeff"], cfg.load_balance_coeff,
                         balance["iterations"]))
    rate = assumed.get("learning_rate", 1e-4)
    if assumed.get("warmup_steps"):
        rate = optax.linear_schedule(0.0, rate, assumed["warmup_steps"])
    return Built(
        params=params, loss_fn=nemotron_h.make_loss_fn(model),
        optimizer=nemotron_h.make_optimizer(
            rate, cfg.load_balance_coeff,
            weights=getattr(optax, assumed.get("optimizer", "adamw"))),
        pool=pool,
        sample=batches(config, 1, traffic["check_sequences"], seq_len,
                       seed + 1)[0],
        tokens_per_step=global_batch * seq_len,
        train_flops_per_token=flops_nemotron_h.train_flops_per_token(
            config, seq_len),
        kernel_cost_per_step=(
            flops_nemotron_h.kernel_cost_per_step(config, traffic)
            if config.get("expects_pallas") else None),
        reference_config={
            "pattern": cfg.pattern, "mamba_heads": cfg.mamba_heads,
            "mamba_head_dim": cfg.mamba_head_dim, "n_groups": cfg.n_groups,
            "d_state": cfg.d_state, "n_heads": cfg.n_heads,
            "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "top_k": cfg.top_k, "rms_eps": cfg.rms_eps,
            "route_norm": cfg.route_norm, "route_scale": cfg.route_scale,
            "route_eps": cfg.route_eps,
            "first_expert_held": cfg.first_expert_held})
