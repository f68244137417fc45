"""MiMo-V2 (``autodist_tpu/models/mimo_v2.py``) from its published config
file, cut to one expert-parallel rank's share and stored as quarters over the
cell's four chips: builds the model, its loss, the optimizer that carries the
expert-bias rule, the seeded host batch pool and the required-operations
counts (``benchmark/flops_mimo_v2.py``).

No chip holds this configuration's state whole (2.22B float32 parameters =
8.9 GB beside gradients and Adam's moments), so, as ``families/jamba.py``
does and with its ``stored_layout``: **the parameters are made as shares**
(``init`` under a jit whose outputs are laid out as ``strategy.FullySharded``'s
plan will store them), and **the loss function brings its mesh where none is
ambient** (the job's check against the reference), with
``parallel/mesh.py`` ``stored_shards`` so that the kernels under
``per_device`` take a stored leaf (here the expert banks) as its share.
**The balancing rule before the first step runs on those quarters**: inside
the cell's mesh and ``stored_shards``, on token batches split over the data
axis, so its forward passes are the step's own forward (a sequence a chip,
the banks gathered in the share's body) and the loads it reads are the
global batch's. On one device every layout is the whole leaf and all of this
is a no-op.
"""

from benchmark import flops_mimo_v2
from benchmark.families.common import Built
from benchmark.families.jamba import stored_layout
from benchmark.families.transformer_lm import batches   # the same LM batches

# What models/mimo_v2.py computes and no option of it changes: a configuration
# that says otherwise is another model.
COMPUTED = (("model_type", "mimo_v2"), ("hidden_act", "silu"),
            ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
            ("attention_bias", False), ("tie_word_embeddings", False),
            ("n_group", 1), ("topk_group", 1), ("norm_topk_prob", True),
            ("n_shared_experts", None), ("routed_scaling_factor", None),
            ("add_swa_attention_sink_bias", True),
            ("add_full_attention_sink_bias", False))
# ... and the keys a sliding layer repeats, which it computes at the full
# layers' values (query heads, both widths, the window under its second name)
BOTH_KINDS = (("swa_num_attention_heads", "num_attention_heads"),
              ("swa_head_dim", "head_dim"), ("swa_v_head_dim", "v_head_dim"),
              ("sliding_window_size", "sliding_window"))


def model_config(config: dict):
    import jax.numpy as jnp

    from autodist_tpu.models import mimo_v2
    assumed = config.get("assumed", {})
    for key, computed in COMPUTED + tuple(
            (key, config[same]) for key, same in BOTH_KINDS):
        if config[key] != computed:
            raise ValueError(f"models/mimo_v2.py computes {key} = {computed!r}, "
                             f"the configuration says {config[key]!r}")
    if config["rope_scaling"]["rope_type"] != "default":
        raise ValueError("models/mimo_v2.py computes no rope scaling")
    layers = config["num_hidden_layers"]
    if not len(config["hybrid_layer_pattern"]) == len(config["moe_layer_freq"]) \
            == layers:
        raise ValueError("hybrid_layer_pattern and moe_layer_freq must name "
                         "num_hidden_layers layers")
    return mimo_v2.MimoV2Config(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        swa_n_kv_heads=config["swa_num_key_value_heads"],
        head_dim=config["head_dim"], v_head_dim=config["v_head_dim"],
        layer_pattern=tuple(config["hybrid_layer_pattern"]),
        moe_layer_freq=tuple(config["moe_layer_freq"]),
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        n_experts_routed=config["router_width"],
        experts_held=config["n_routed_experts"],
        first_expert_held=config["first_expert_held"],
        top_k=config["num_experts_per_tok"], window=config["sliding_window"],
        rows_bound=assumed.get("rows_bound"),
        route_eps=assumed.get("route_eps", 1e-20),
        load_balance_coeff=assumed.get("load_balance_coeff", 1e-3),
        partial_rotary_factor=config["partial_rotary_factor"],
        rope_theta=float(config["rope_theta"]),
        swa_rope_theta=float(config["swa_rope_theta"]),
        value_scale=config["attention_value_scale"],
        rms_eps=config["layernorm_epsilon"],
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
    from jax.sharding import NamedSharding, PartitionSpec as P

    from autodist_tpu import const
    from autodist_tpu.models import mimo_v2
    from autodist_tpu.parallel.mesh import ambient_mesh, stored_shards

    cfg = model_config(config)
    assumed = config.get("assumed", {})
    model = mimo_v2.MimoV2(cfg)
    init = lambda key: mimo_v2.init_params(cfg, rng=key)[1]  # noqa: E731
    key = jax.random.PRNGKey(seed)
    shapes = jax.eval_shape(init, key)
    loss_fn = mimo_v2.make_loss_fn(model)
    seq_len = traffic["seq_len"]
    pool = batches(config, traffic["pool_batches"], global_batch, seq_len, seed)
    if abstract:
        params = shapes
    else:
        mesh, layout, axes = stored_layout(shapes, traffic)
        params = jax.jit(init, out_shardings=layout)(key)
        balance = assumed.get("expert_bias_balance")
        if balance:
            # a trained router's loads, not a random one's: the balancing
            # rule alone on the seeded pool, its coefficient falling to the
            # published one; on the quarters, a sequence a chip
            rows = NamedSharding(mesh, P(const.MESH_AXIS_DATA)
                                 if global_batch % mesh.size == 0 else P())
            tokens = [jax.device_put(b["tokens"][:, :-1], rows) for b in pool]
            with mesh, stored_shards(axes):
                params = mimo_v2.balance_expert_bias(
                    model, params, tokens,
                    np.geomspace(balance["first_coeff"], cfg.load_balance_coeff,
                                 balance["iterations"]))
        model_loss = loss_fn

        def loss_fn(params, batch):
            if ambient_mesh() is not None:
                return model_loss(params, batch)
            with mesh, stored_shards(axes):
                return model_loss(jax.tree_util.tree_map(
                    jax.lax.with_sharding_constraint, params, layout), batch)

    rate = assumed.get("learning_rate", 1e-4)
    if assumed.get("warmup_steps"):
        rate = optax.linear_schedule(0.0, rate, assumed["warmup_steps"])
    return Built(
        params=params, loss_fn=loss_fn,
        optimizer=mimo_v2.make_optimizer(
            rate, cfg.load_balance_coeff,
            weights=getattr(optax, assumed.get("optimizer", "adamw"))),
        pool=pool,
        sample=batches(config, 1, traffic["check_sequences"], seq_len,
                       seed + 1)[0],
        tokens_per_step=global_batch * seq_len,
        train_flops_per_token=flops_mimo_v2.train_flops_per_token(config, seq_len),
        kernel_cost_per_step=(
            flops_mimo_v2.kernel_cost_per_step(config, traffic)
            if config.get("expects_pallas") else None),
        reference_config={
            "layer_pattern": cfg.layer_pattern,
            "dense": tuple(not moe for moe in cfg.moe_layer_freq),
            "n_heads": cfg.n_heads, "head_dim": cfg.head_dim,
            "v_head_dim": cfg.v_head_dim, "window": cfg.window,
            "rotary_dim": cfg.rotary_dim, "rope_theta": cfg.rope_theta,
            "swa_rope_theta": cfg.swa_rope_theta,
            "value_scale": cfg.value_scale, "top_k": cfg.top_k,
            "rms_eps": cfg.rms_eps,
            "first_expert_held": cfg.first_expert_held})
