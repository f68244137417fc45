"""Jamba (``autodist_tpu/models/jamba.py``) from its published config file:
builds the model, its loss, the optimizer, the seeded host batch pool and the
required-operations counts (``benchmark/flops_jamba.py``).

No chip holds this configuration's parameters whole (1.6B float32 = 6.4 GB
beside the step's state), so two things are this file's and no other
family's:

* **the parameters are made as shares.** ``init`` runs under a jit whose
  outputs are laid out over the cell's mesh as the plan of the traffic
  file's strategy will store them (``strategy/partition_utils.py``
  ``data_shard_axis``: a function of the shape alone, shared with
  ``strategy.FullySharded``), on a mesh built as the runner builds its own
  (``parallel/mesh.py`` ``build_mesh``, the runner's axis names). The
  caller's copy is then a share a chip, and ``runner.init`` moves nothing
  between chips.
* **the loss function brings its mesh where none is ambient.** The runner
  traces a step under ``with mesh:``; the job's check against the reference
  jits ``value_and_grad(loss_fn)`` with no mesh in scope, where
  ``parallel/mesh.py`` ``per_device`` (which reads the ambient mesh at trace
  time) would call the Mosaic kernels unwrapped and the compiler would
  refuse to partition them. There the loss enters the cell's mesh itself,
  says which leaves are stored as shares (``stored_shards``) and holds the
  parameters to their stored layout, so the gradients come out as shares too
  and never whole. Under an ambient mesh it is the model's loss untouched.

On one device (the tests' CPU rehearsal, a one-chip traffic file) every
layout is the whole leaf and both are no-ops.
"""

from benchmark import flops_jamba
from benchmark.families.common import Built, optimizer
from benchmark.families.transformer_lm import batches   # the same LM batches

# What models/jamba.py computes and no option of it changes: a configuration
# that says otherwise is another model.
COMPUTED = (("model_type", "jamba"), ("hidden_act", "silu"),
            ("mamba_conv_bias", True), ("mamba_proj_bias", False),
            ("num_experts", 1), ("num_experts_per_tok", 1),
            ("tie_word_embeddings", True), ("sliding_window", None))


def model_config(config: dict):
    import jax.numpy as jnp

    from autodist_tpu.models import jamba
    assumed = config.get("assumed", {})
    for key, computed in COMPUTED:
        if config[key] != computed:
            raise ValueError(f"models/jamba.py computes {key} = {computed!r}, "
                             f"the configuration says {config[key]!r}")
    return jamba.JambaConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        attn_period=config["attn_layer_period"],
        attn_offset=config["attn_layer_offset"],
        mamba_expand=config["mamba_expand"], d_state=config["mamba_d_state"],
        dt_rank=config["mamba_dt_rank"], conv_kernel=config["mamba_d_conv"],
        chunk=assumed.get("scan_chunk", 128),
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        time_step_min=assumed.get("time_step_min", 0.001),
        time_step_max=assumed.get("time_step_max", 0.1),
        time_step_floor=assumed.get("time_step_floor", 1e-4),
        rms_eps=config["rms_norm_eps"],
        max_len=config["max_position_embeddings"],
        dtype=jnp.dtype(assumed.get("activation_dtype", "bfloat16")),
        attention_impl=assumed.get("attention_impl", "dot"),
        ssm_impl=assumed.get("ssm_impl", "xla"),
        fused_head=bool(assumed.get("fused_head", False)),
        remat=bool(assumed.get("remat", False)))


def stored_layout(shapes, traffic: dict):
    """``(mesh, a NamedSharding a leaf, {shape: tensor axis} of the leaves
    stored as shares)`` for the cell's mesh over the first devices, as
    ``strategy.FullySharded``'s plan will store them; whole leaves under any
    other strategy."""
    import math

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from autodist_tpu import const
    from autodist_tpu.parallel.mesh import build_mesh
    from autodist_tpu.strategy.partition_utils import data_shard_axis

    chips = math.prod(traffic["mesh"].values())
    mesh = build_mesh(axes=traffic["mesh"], devices=jax.devices()[:chips])
    dp = mesh.shape[const.MESH_AXIS_DATA] \
        if traffic["strategy"] == "FullySharded" else 1
    axes = {}

    def layout(leaf):
        axis = data_shard_axis(leaf.shape, dp)
        if axis is None:
            return NamedSharding(mesh, P())
        axes[tuple(leaf.shape)] = axis
        return NamedSharding(mesh, P(*([None] * axis), const.MESH_AXIS_DATA))

    return mesh, jax.tree_util.tree_map(layout, shapes), axes


def build(config: dict, traffic: dict, seed: int, global_batch: int,
          abstract: bool = False) -> Built:
    """``abstract=True`` gives the parameters as shapes (``jax.eval_shape``),
    for the compile rehearsal, which has no device to hold them."""
    import jax

    from autodist_tpu.models import jamba
    from autodist_tpu.parallel.mesh import ambient_mesh, stored_shards

    cfg = model_config(config)
    model = jamba.Jamba(cfg)
    init = lambda key: jamba.init_params(cfg, rng=key)[1]  # noqa: E731
    key = jax.random.PRNGKey(seed)
    shapes = jax.eval_shape(init, key)
    loss_fn = jamba.make_loss_fn(model)
    if abstract:
        params = shapes
    else:
        mesh, layout, axes = stored_layout(shapes, traffic)
        params = jax.jit(init, out_shardings=layout)(key)
        model_loss = loss_fn

        def loss_fn(params, batch):
            if ambient_mesh() is not None:
                return model_loss(params, batch)
            with mesh, stored_shards(axes):
                return model_loss(jax.tree_util.tree_map(
                    jax.lax.with_sharding_constraint, params, layout), batch)

    seq_len = traffic["seq_len"]
    return Built(
        params=params, loss_fn=loss_fn, optimizer=optimizer(config, "adamw"),
        pool=batches(config, traffic["pool_batches"], global_batch, seq_len,
                     seed),
        sample=batches(config, 1, traffic["check_sequences"], seq_len,
                       seed + 1)[0],
        tokens_per_step=global_batch * seq_len,
        train_flops_per_token=flops_jamba.train_flops_per_token(config, seq_len),
        kernel_cost_per_step=(flops_jamba.kernel_cost_per_step(config, traffic)
                              if config.get("expects_pallas") else None),
        reference_config={
            "n_layers": cfg.n_layers, "attn_period": cfg.attn_period,
            "attn_offset": cfg.attn_offset, "d_state": cfg.d_state,
            "dt_rank": cfg.dt_rank, "n_heads": cfg.n_heads,
            "n_kv_heads": cfg.n_kv_heads, "rms_eps": cfg.rms_eps})
