"""DeepSeek-V3 family / Kanana-2 (``autodist_tpu/models/deepseek_v3.py``) from
its published config file, cut to one chip's share: builds the model, its
loss, the optimizer that carries the expert-bias rule, the seeded host batch
pool and the required-operations counts (``benchmark/flops_deepseek_v3.py``)."""

from benchmark import flops_deepseek_v3
from benchmark.families.common import Built
from benchmark.families.transformer_lm import batches   # the same LM batches

# What models/deepseek_v3.py computes and no option of it changes: a
# configuration that says otherwise is another model.
COMPUTED = (("model_type", "deepseek_v3"), ("hidden_act", "silu"),
            ("attention_bias", False), ("q_lora_rank", None),
            ("rope_scaling", None), ("rope_interleave", True),
            ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
            ("n_group", 1), ("topk_group", 1), ("moe_layer_freq", 1),
            ("tie_word_embeddings", False))


def model_config(config: dict):
    import jax.numpy as jnp

    from autodist_tpu.models import deepseek_v3
    assumed = config.get("assumed", {})
    for key, computed in COMPUTED:
        if config[key] != computed:
            raise ValueError(f"models/deepseek_v3.py computes {key} = "
                             f"{computed!r}, the configuration says "
                             f"{config[key]!r}")
    if config["qk_head_dim"] != (config["qk_nope_head_dim"]
                                 + config["qk_rope_head_dim"]):
        raise ValueError("qk_head_dim must be the sum of its two parts")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention has a key head a query head")
    if config["head_dim"] != config["qk_rope_head_dim"]:
        raise ValueError("the family's head_dim is the rotary part's width")
    return deepseek_v3.DeepseekV3Config(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], kv_lora_rank=config["kv_lora_rank"],
        n_dense_layers=config["first_k_dense_replace"],
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        n_experts_routed=config["router_width"],
        experts_held=config["n_routed_experts"],
        first_expert_held=config["first_expert_held"],
        top_k=config["num_experts_per_tok"],
        n_shared_experts=config["n_shared_experts"],
        rows_bound=assumed.get("rows_bound"),
        route_norm=config["norm_topk_prob"],
        route_scale=float(config["routed_scaling_factor"]),
        route_eps=assumed.get("route_eps", 1e-20),
        load_balance_coeff=assumed.get("load_balance_coeff", 1e-3),
        rope_theta=float(config["rope_theta"]), rms_eps=config["rms_norm_eps"],
        max_len=config["max_position_embeddings"],
        dtype=jnp.dtype(assumed.get("activation_dtype", "bfloat16")),
        attention_impl=assumed.get("attention_impl", "dot"),
        fused_head=bool(assumed.get("fused_head", False)),
        remat=bool(assumed.get("remat", False)))


def build(config: dict, traffic: dict, seed: int, global_batch: int,
          abstract: bool = False) -> Built:
    """``abstract=True`` gives the parameters as shapes (``jax.eval_shape``),
    for the compile rehearsal, which has no device to hold them."""
    import jax
    import numpy as np
    import optax

    from autodist_tpu.models import deepseek_v3

    cfg = model_config(config)
    assumed = config.get("assumed", {})
    model = deepseek_v3.DeepseekV3(cfg)
    init = lambda key: deepseek_v3.init_params(cfg, rng=key)[1]  # noqa: E731
    key = jax.random.PRNGKey(seed)
    params = jax.eval_shape(init, key) if abstract else init(key)
    seq_len = traffic["seq_len"]
    pool = batches(config, traffic["pool_batches"], global_batch, seq_len, seed)
    balance = assumed.get("expert_bias_balance")
    if balance and not abstract:
        # a trained router's loads, not a random one's: the balancing rule
        # alone on the seeded pool, its coefficient falling to the trained one
        params = deepseek_v3.balance_expert_bias(
            model, params, [jax.numpy.asarray(b["tokens"][:, :-1]) for b in pool],
            np.geomspace(balance["first_coeff"], cfg.load_balance_coeff,
                         balance["iterations"]))
    rate = assumed.get("learning_rate", 1e-4)
    if assumed.get("warmup_steps"):
        rate = optax.linear_schedule(0.0, rate, assumed["warmup_steps"])
    return Built(
        params=params, loss_fn=deepseek_v3.make_loss_fn(model),
        optimizer=deepseek_v3.make_optimizer(
            rate, cfg.load_balance_coeff,
            weights=getattr(optax, assumed.get("optimizer", "adamw"))),
        pool=pool,
        sample=batches(config, 1, traffic["check_sequences"], seq_len,
                       seed + 1)[0],
        tokens_per_step=global_batch * seq_len,
        train_flops_per_token=flops_deepseek_v3.train_flops_per_token(
            config, seq_len),
        kernel_cost_per_step=(
            flops_deepseek_v3.kernel_cost_per_step(config, traffic)
            if config.get("expects_pallas") else None),
        reference_config={
            "n_heads": cfg.n_heads, "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
            "n_layers": cfg.n_layers, "n_dense_layers": cfg.n_dense_layers,
            "top_k": cfg.top_k, "rms_eps": cfg.rms_eps,
            "rope_theta": cfg.rope_theta, "route_norm": cfg.route_norm,
            "route_scale": cfg.route_scale,
            "first_expert_held": cfg.first_expert_held})
