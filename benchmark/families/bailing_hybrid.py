"""Ling / Ring hybrid family / Ling-3.0-flash
(``autodist_tpu/models/bailing_hybrid.py``) from its published config file,
cut to one chip's share of each layer's heads and experts: builds the model,
its loss, the optimizer that carries the expert-bias rule, the seeded host
batch pool and the required-operations counts
(``benchmark/flops_bailing_hybrid.py``)."""

from benchmark import flops_bailing_hybrid
from benchmark.families.common import Built
from benchmark.families.evabyte import batches   # ids uniform over the held rows

# What models/bailing_hybrid.py computes and no option of it changes: a
# configuration that says otherwise is another model.
COMPUTED = (("model_type", "bailing_hybrid"), ("hidden_act", "silu"),
            ("use_bias", False), ("use_qkv_bias", False), ("q_lora_rank", None),
            ("rope_scaling", None), ("rope_interleave", True),
            ("score_function", "sigmoid"), ("scoring_func", "sigmoid"),
            ("topk_method", "noaux_tc"), ("moe_router_enable_expert_bias", True),
            ("scale_router_input", False), ("linear_silu", True),
            ("use_qk_norm", True), ("value_norm", False), ("kda_safe_gate", True),
            ("no_kda_lora", True), ("use_kda_lora", False),
            ("group_norm_size", 1), ("num_kv_heads_for_linear_attn", 0),
            ("gated_attention_proj_granularity_type", "head_wise"),
            ("use_mla_nope", False), ("use_nGPT", False),
            ("up_proj_norm", False), ("num_shared_experts", 1),
            ("tie_word_embeddings", False))


def model_config(config: dict):
    import jax.numpy as jnp

    from autodist_tpu.models import bailing_hybrid
    assumed = config.get("assumed", {})
    for key, computed in COMPUTED:
        if config[key] != computed:
            raise ValueError(f"models/bailing_hybrid.py computes {key} = "
                             f"{computed!r}, the configuration says "
                             f"{config[key]!r}")
    if config["qk_head_dim"] != (config["qk_nope_head_dim"]
                                 + config["qk_rope_head_dim"]):
        raise ValueError("qk_head_dim must be the sum of its two parts")
    if config["rotary_dim"] != config["qk_rope_head_dim"]:
        raise ValueError("the rotary columns are the key's rotary part")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("both mixers have a key head a query head")
    kinds = tuple(config["layer_types"])
    if len(kinds) != config["num_hidden_layers"] or any(
            kind != ("mla" if (i + 1) % config["layer_group_size"] == 0
                     else "kda") for i, kind in enumerate(kinds)):
        raise ValueError("layer_types must be the model's own first layers: "
                         "latent attention where (i + 1) % layer_group_size "
                         "== 0, kda elsewhere")
    clamped = [i for i in range(config["num_hidden_layers"]) if
               config["expert_swiglu_limit_list"][i]
               or config["share_expert_swiglu_limit_list"][i]]
    if clamped:
        raise ValueError(f"layers {clamped} clamp their experts' products; "
                         f"models/bailing_hybrid.py has no clamp")
    return bailing_hybrid.BailingHybridConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        layer_group_size=config["layer_group_size"], layer_types=kinds,
        n_heads=config["layer_heads"],
        heads_held=config["num_attention_heads"],
        first_head_held=config["first_head_held"], head_dim=config["head_dim"],
        conv_kernel=config["short_conv_kernel_size"],
        kda_lower_bound=float(config["kda_lower_bound"]),
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], kv_lora_rank=config["kv_lora_rank"],
        n_dense_layers=config["first_k_dense_replace"],
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["moe_shared_expert_intermediate_size"],
        n_experts_routed=config["router_width"],
        experts_held=config["num_experts"],
        first_expert_held=config["first_expert_held"],
        top_k=config["num_experts_per_tok"], n_group=config["n_group"],
        topk_group=config["topk_group"], rows_bound=assumed.get("rows_bound"),
        route_norm=config["norm_topk_prob"],
        route_scale=float(config["routed_scaling_factor"]),
        route_eps=assumed.get("route_eps", 1e-20),
        load_balance_coeff=assumed.get("load_balance_coeff", 1e-3),
        rope_theta=float(config["rope_theta"]), rms_eps=config["rms_norm_eps"],
        max_len=config["max_position_embeddings"],
        dtype=jnp.dtype(assumed.get("activation_dtype", "bfloat16")),
        attention_impl=assumed.get("attention_impl", "dot"),
        kda_impl=assumed.get("kda_impl", "xla"),
        kda_chunk=assumed.get("kda_chunk", 64),
        fused_head=bool(assumed.get("fused_head", False)),
        remat=bool(assumed.get("remat", False)))


def build(config: dict, traffic: dict, seed: int, global_batch: int,
          abstract: bool = False) -> Built:
    """``abstract=True`` gives the parameters as shapes (``jax.eval_shape``),
    for the compile rehearsal, which has no device to hold them."""
    import jax
    import numpy as np
    import optax

    from autodist_tpu.models import bailing_hybrid

    cfg = model_config(config)
    assumed = config.get("assumed", {})
    model = bailing_hybrid.BailingHybrid(cfg)
    init = lambda key: bailing_hybrid.init_params(cfg, rng=key)[1]  # noqa: E731
    key = jax.random.PRNGKey(seed)
    params = jax.eval_shape(init, key) if abstract else init(key)
    seq_len = traffic["seq_len"]
    pool = batches(config, traffic["pool_batches"], global_batch, seq_len, seed)
    balance = assumed.get("expert_bias_balance")
    if balance and not abstract:
        # a trained router's loads, not a random one's: the balancing rule
        # alone on the seeded pool, its coefficient falling to the trained one
        params = bailing_hybrid.balance_expert_bias(
            model, params, [jax.numpy.asarray(b["tokens"][:, :-1]) for b in pool],
            np.geomspace(balance["first_coeff"], cfg.load_balance_coeff,
                         balance["iterations"]))
    rate = assumed.get("learning_rate", 1e-4)
    if assumed.get("warmup_steps"):
        rate = optax.linear_schedule(0.0, rate, assumed["warmup_steps"])
    return Built(
        params=params, loss_fn=bailing_hybrid.make_loss_fn(model),
        optimizer=bailing_hybrid.make_optimizer(
            rate, cfg.load_balance_coeff,
            weights=getattr(optax, assumed.get("optimizer", "adamw"))),
        pool=pool,
        sample=batches(config, 1, traffic["check_sequences"], seq_len,
                       seed + 1)[0],
        tokens_per_step=global_batch * seq_len,
        train_flops_per_token=flops_bailing_hybrid.train_flops_per_token(
            config, seq_len),
        kernel_cost_per_step=(
            flops_bailing_hybrid.kernel_cost_per_step(config, traffic)
            if config.get("expects_pallas") else None),
        reference_config={
            "layer_types": cfg.kinds, "n_dense_layers": cfg.n_dense_layers,
            "n_heads": cfg.heads_held, "head_dim": cfg.head_dim,
            "kda_lower_bound": cfg.kda_lower_bound,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
            "top_k": cfg.top_k, "n_group": cfg.n_group,
            "topk_group": cfg.topk_group, "rms_eps": cfg.rms_eps,
            "rope_theta": cfg.rope_theta, "route_norm": cfg.route_norm,
            "route_scale": cfg.route_scale,
            "first_expert_held": cfg.first_expert_held})
