"""LFM2-MoE (``autodist_tpu/models/lfm2_moe.py``) from its published config
file, cut to one chip's share: builds the model, its loss, the optimizer that
carries the expert-bias rule, the seeded host batch pool and the
required-operations counts (``benchmark/flops_lfm2.py``)."""

from benchmark import flops_lfm2
from benchmark.families.common import Built
from benchmark.families.transformer_lm import batches   # the same LM batches


def model_config(config: dict):
    import jax.numpy as jnp

    from autodist_tpu.models import lfm2_moe
    assumed = config.get("assumed", {})
    for key, computed in (("conv_bias", False), ("use_expert_bias", True),
                          ("model_type", "lfm2_moe")):
        if config[key] != computed:
            raise ValueError(f"models/lfm2_moe.py computes {key} = {computed!r}, "
                             f"the configuration says {config[key]!r}")
    if config["rope_parameters"]["rope_type"] != "default":
        raise ValueError("models/lfm2_moe.py computes the default rotary "
                         "embedding only")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("layer_types must name num_hidden_layers layers")
    return lfm2_moe.Lfm2MoeConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        layer_types=tuple(config["layer_types"]),
        n_dense_layers=config["num_dense_layers"],
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        n_experts_routed=config["router_width"],
        experts_held=config["num_experts"],
        first_expert_held=config["first_expert_held"],
        top_k=config["num_experts_per_tok"],
        conv_kernel=config["conv_L_cache"], rows_bound=assumed.get("rows_bound"),
        route_norm=config["norm_topk_prob"],
        route_scale=float(config["routed_scaling_factor"]),
        route_eps=assumed.get("route_eps", 1e-6),
        load_balance_coeff=assumed.get("load_balance_coeff", 1e-3),
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        rms_eps=config["norm_eps"], max_len=config["max_position_embeddings"],
        dtype=jnp.dtype(assumed.get("activation_dtype", "bfloat16")),
        attention_impl=assumed.get("attention_impl", "dot"),
        conv_impl=assumed.get("conv_impl", "xla"),
        fused_head=bool(assumed.get("fused_head", False)))


def build(config: dict, traffic: dict, seed: int, global_batch: int,
          abstract: bool = False) -> Built:
    """``abstract=True`` gives the parameters as shapes (``jax.eval_shape``),
    for the compile rehearsal, which has no device to hold them."""
    import jax
    import numpy as np
    import optax

    from autodist_tpu.models import lfm2_moe

    cfg = model_config(config)
    assumed = config.get("assumed", {})
    model = lfm2_moe.Lfm2Moe(cfg)
    init = lambda key: lfm2_moe.init_params(cfg, rng=key)[1]  # noqa: E731
    key = jax.random.PRNGKey(seed)
    params = jax.eval_shape(init, key) if abstract else init(key)
    seq_len = traffic["seq_len"]
    pool = batches(config, traffic["pool_batches"], global_batch, seq_len, seed)
    balance = assumed.get("expert_bias_balance")
    if balance and not abstract:
        # a trained router's loads, not a random one's: the balancing rule
        # alone on the seeded pool, its coefficient falling to the trained one
        params = lfm2_moe.balance_expert_bias(
            model, params, [jax.numpy.asarray(b["tokens"][:, :-1]) for b in pool],
            np.geomspace(balance["first_coeff"], cfg.load_balance_coeff,
                         balance["iterations"]))
    rate = assumed.get("learning_rate", 1e-4)
    if assumed.get("warmup_steps"):
        rate = optax.linear_schedule(0.0, rate, assumed["warmup_steps"])
    return Built(
        params=params, loss_fn=lfm2_moe.make_loss_fn(model),
        optimizer=lfm2_moe.make_optimizer(
            rate, cfg.load_balance_coeff,
            weights=getattr(optax, assumed.get("optimizer", "adamw"))),
        pool=pool,
        sample=batches(config, 1, traffic["check_sequences"], seq_len,
                       seed + 1)[0],
        tokens_per_step=global_batch * seq_len,
        train_flops_per_token=flops_lfm2.train_flops_per_token(config, seq_len),
        kernel_cost_per_step=(
            flops_lfm2.kernel_cost_per_step(config, traffic)
            if config.get("expects_pallas") else None),
        reference_config={
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "layer_types": cfg.layer_types,
            "n_dense_layers": cfg.n_dense_layers, "top_k": cfg.top_k,
            "rms_eps": cfg.rms_eps, "rope_theta": cfg.rope_theta,
            "route_norm": cfg.route_norm, "route_scale": cfg.route_scale,
            "route_eps": cfg.route_eps,
            "first_expert_held": cfg.first_expert_held})
