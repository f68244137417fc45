"""AutoStrategy: the analytic cost model picks the right regime per parameter.

The reference has no auto builder (its default is a fixed PSLoadBalancing,
``autodist.py:70``; auto-learning is named as future work in its tutorials), so
these tests pin this builder's own decision contract: regime by memory budget,
sparse->PS, large->partitioned, codec by node count/bandwidth — and that the
emitted strategy trains value-exactly like the fixed builder it reduces to.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import AutoDist
from autodist_tpu.model_spec import ModelSpec
from autodist_tpu.proto import strategy_pb2
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy import AllReduce, AutoStrategy

AR = strategy_pb2.AllReduceSynchronizer


def _spec(yaml_text=None):
    return ResourceSpec(yaml_text) if yaml_text else ResourceSpec(
        "nodes: [{address: localhost, tpus: 8, chief: true}]")


def _dense_params(n=3, dim=16):
    rng = np.random.RandomState(0)
    return {f"w{i}": rng.randn(dim, dim).astype(np.float32) for i in range(n)}


def _which(node):
    return node.WhichOneof("synchronizer")


def test_small_dense_model_goes_allreduce():
    strategy = AutoStrategy().build(ModelSpec(_dense_params()), _spec())
    kinds = {n.var_name: _which(n) for n in strategy.proto.node_config}
    assert set(kinds.values()) == {"all_reduce_synchronizer"}
    axes = {a.name: a.size for a in strategy.proto.mesh_config.axes}
    assert axes.get("data") == 8
    assert axes.get("reduce", 1) == 1


def test_memory_bound_model_goes_ps():
    # 3 x 1 MiB params with a 1 MiB budget -> PS/ZeRO regime.
    params = {f"w{i}": np.zeros((512, 512), np.float32) for i in range(3)}
    strategy = AutoStrategy(memory_budget_bytes=1 << 20).build(
        ModelSpec(params), _spec())
    kinds = {_which(n) for n in strategy.proto.node_config}
    assert kinds == {"ps_synchronizer"}
    axes = {a.name: a.size for a in strategy.proto.mesh_config.axes}
    assert axes.get("reduce") == 8  # ZeRO sharding across all devices


def test_sparse_param_goes_ps_dense_goes_ar():
    params = {"emb": np.zeros((100, 8), np.float32),
              "w": np.zeros((8, 8), np.float32)}
    strategy = AutoStrategy().build(
        ModelSpec(params, sparse_names=["emb"]), _spec())
    kinds = {n.var_name: _which(n) for n in strategy.proto.node_config}
    assert kinds["emb"] == "ps_synchronizer"
    assert kinds["w"] == "all_reduce_synchronizer"


def test_large_param_is_partitioned():
    params = {"big": np.zeros((4096, 4096), np.float32),   # 64 MiB
              "small": np.zeros((8, 8), np.float32)}
    builder = AutoStrategy(partition_threshold_bytes=32 << 20)
    strategy = builder.build(ModelSpec(params), _spec())
    nodes = {n.var_name: n for n in strategy.proto.node_config}
    assert max(nodes["big"].partitioner.num_shards) >= 2
    assert len(nodes["big"].part_config) >= 2
    assert not nodes["small"].partitioner.num_shards
    assert "partition threshold" in builder.explain()
    # The mesh carves a real model axis so the sharding is physical, and the
    # shard count matches it (64 MiB / 32 MiB threshold -> 2-way).
    axes = {a.name: a.size for a in strategy.proto.mesh_config.axes}
    assert axes.get("model") == 2
    assert max(nodes["big"].partitioner.num_shards) == 2


def test_multinode_low_bandwidth_picks_compressed_dcn():
    yaml_two_nodes = """
nodes:
  - {address: 10.0.0.1, tpus: 4, chief: true, network_bandwidth: 10}
  - {address: 10.0.0.2, tpus: 4, network_bandwidth: 10}
"""
    strategy = AutoStrategy().build(ModelSpec(_dense_params()), _spec(yaml_two_nodes))
    for node in strategy.proto.node_config:
        assert node.all_reduce_synchronizer.spec == AR.DCN
        assert node.all_reduce_synchronizer.compressor == AR.BF16_EF


def test_multinode_fast_link_stays_uncompressed():
    yaml_two_nodes = """
nodes:
  - {address: 10.0.0.1, tpus: 4, chief: true, network_bandwidth: 400}
  - {address: 10.0.0.2, tpus: 4, network_bandwidth: 400}
"""
    strategy = AutoStrategy().build(ModelSpec(_dense_params()), _spec(yaml_two_nodes))
    for node in strategy.proto.node_config:
        assert node.all_reduce_synchronizer.compressor == AR.NONE


def test_multinode_unspecified_bandwidth_stays_lossless():
    """No stated network_bandwidth: the defaulted 1 GBE value must NOT buy a
    numerics-changing lossy codec — hierarchical reduce yes, compression no."""
    yaml_two_nodes = """
nodes:
  - {address: 10.0.0.1, tpus: 4, chief: true}
  - {address: 10.0.0.2, tpus: 4}
"""
    builder = AutoStrategy()
    strategy = builder.build(ModelSpec(_dense_params()), _spec(yaml_two_nodes))
    for node in strategy.proto.node_config:
        assert node.all_reduce_synchronizer.spec == AR.DCN
        assert node.all_reduce_synchronizer.compressor == AR.NONE
    assert "bandwidth unspecified" in builder.explain()


def test_multinode_dcn_carves_inner_mesh_axis():
    """The DCN knob needs a populated inner DP axis: AutoStrategy's emitted
    mesh must be {reduce: chips/node, data: nodes}, not {data: all}."""
    yaml_two_nodes = """
nodes:
  - {address: 10.0.0.1, tpus: 4, chief: true, network_bandwidth: 400}
  - {address: 10.0.0.2, tpus: 4, network_bandwidth: 400}
"""
    strategy = AutoStrategy().build(ModelSpec(_dense_params()), _spec(yaml_two_nodes))
    axes = {a.name: a.size for a in strategy.proto.mesh_config.axes}
    assert axes.get("reduce") == 4   # intra-node ICI tier
    assert axes.get("data") == 2     # cross-node DCN tier


def test_autostrategy_dcn_lowering_is_hierarchical():
    """End-to-end: the strategy AutoStrategy emits for a 2x4 multi-node spec
    actually lowers to the two-phase reduce (the knob is honored, not inert),
    and gradients stay value-exact vs the single-node AllReduce lowering."""
    from autodist_tpu.parallel import synchronization
    from autodist_tpu.parallel.mesh import build_mesh
    from autodist_tpu.parallel.plan import ShardingPlan

    yaml_two_nodes = """
nodes:
  - {address: 10.0.0.1, tpus: 4, chief: true, network_bandwidth: 400}
  - {address: 10.0.0.2, tpus: 4, network_bandwidth: 400}
"""
    rng = np.random.RandomState(2)
    params = {f"w{i}": jnp.asarray(rng.randn(8, 4), jnp.float32)
              for i in range(3)}
    batch = {"x": rng.randn(16, 8).astype(np.float32),
             "y": rng.randn(16, 4).astype(np.float32)}

    def loss(p, b):
        out = sum((i + 1.0) * (b["x"] @ p[k]) for i, k in enumerate(sorted(p)))
        return jnp.mean((b["y"] - out) ** 2)

    def lower(builder, spec):
        model = ModelSpec.from_loss_fn(loss, params, batch)
        strategy = builder.build(model, spec)
        plan = ShardingPlan.from_strategy(strategy, model)
        mesh = build_mesh(axes=dict(plan.mesh_axes))
        grad_fn = synchronization.make_grad_fn(plan, model, mesh, loss)
        ef = synchronization.init_ef_state(plan, params, mesh=mesh)
        text = jax.jit(grad_fn).lower(params, batch, ef).as_text()
        with mesh:
            grads, *_ = jax.jit(grad_fn)(params, batch, ef)
        return grads, text

    g_auto, _ = lower(AllReduce(), _spec())
    g_dcn, text = lower(AutoStrategy(), _spec(yaml_two_nodes))
    # Explicit shard_map lowering with the two reduce phases (+1 for the loss);
    # the NONE codec keeps the wire lossless.
    n_reduces = sum("stablehlo.all_reduce" in l for l in text.splitlines())
    assert n_reduces == 3, f"expected 2 hierarchical phases + loss, got {n_reduces}"
    for k in g_auto:
        np.testing.assert_allclose(np.asarray(g_dcn[k]), np.asarray(g_auto[k]),
                                   rtol=1e-5, atol=1e-6)


def test_end_to_end_matches_fixed_builder():
    """Where the model reduces to plain AllReduce, training is value-exact."""
    rng = np.random.RandomState(1)
    params = {"w": rng.randn(4, 1).astype(np.float32), "b": np.zeros((1,), np.float32)}
    batch = {"x": rng.randn(32, 4).astype(np.float32),
             "y": rng.randn(32, 1).astype(np.float32)}

    def loss_fn(p, b):
        return jnp.mean((b["y"] - (b["x"] @ p["w"] + p["b"])) ** 2)

    def run(builder):
        ad = AutoDist(strategy_builder=builder)
        runner = ad.create_distributed_session(loss_fn, params, optax.sgd(0.1),
                                               example_batch=batch)
        state = runner.init(params)
        for _ in range(5):
            state, loss = runner.run(state, batch)
        return jax.device_get(state.params), float(loss)

    p_auto, l_auto = run(AutoStrategy())
    p_ar, l_ar = run(AllReduce())
    for k in p_ar:
        np.testing.assert_allclose(p_auto[k], p_ar[k], rtol=1e-6, atol=1e-6)
    assert l_auto == pytest.approx(l_ar, rel=1e-6)


def test_optimizer_flips_regime_on_same_model():
    """Exact state bytes from eval_shape: the SAME model under the SAME budget
    lands in PS/ZeRO with Adam (params + 2x f32 moments), but AllReduce with
    SGD (no state) and Adafactor (factored moments ~ a few % of params)."""
    params = {f"w{i}": np.zeros((512, 512), np.float32) for i in range(3)}
    budget = 7 << 20   # 3 MiB params; Adam needs ~9 MiB, sgd/adafactor ~3 MiB

    def regime(optimizer):
        b = AutoStrategy(memory_budget_bytes=budget, optimizer=optimizer)
        strategy = b.build(ModelSpec(params), _spec())
        return {_which(n) for n in strategy.proto.node_config}

    assert regime(optax.adam(1e-3)) == {"ps_synchronizer"}
    assert regime(optax.sgd(0.1)) == {"all_reduce_synchronizer"}
    assert regime(optax.adafactor(1e-3)) == {"all_reduce_synchronizer"}


def test_session_hands_optimizer_to_builder():
    """create_distributed_session auto-wires observe_optimizer: no manual
    plumbing, the builder sees the session's optimizer."""
    params = {f"w{i}": np.zeros((512, 512), np.float32) for i in range(3)}
    batch = {"x": np.zeros((8, 512), np.float32)}

    def loss(p, b):
        return sum(jnp.sum((b["x"] @ p[k]) ** 2) for k in p)

    for optimizer, want in ((optax.adam(1e-3), "ps_synchronizer"),
                            (optax.sgd(0.1), "all_reduce_synchronizer")):
        builder = AutoStrategy(memory_budget_bytes=7 << 20)
        ad = AutoDist(None, builder)
        ad.create_distributed_session(loss, params, optimizer,
                                      example_batch=batch)
        kinds = {_which(n) for n in ad._strategy.proto.node_config}
        assert kinds == {want}, (kinds, want)


def test_adafactor_recommendation_when_moments_dominate():
    """Memory-bound WITH Adam where params alone fit: the decision log
    recommends factored moments instead of silently sharding."""
    params = {f"w{i}": np.zeros((512, 512), np.float32) for i in range(3)}
    b = AutoStrategy(memory_budget_bytes=7 << 20, optimizer=optax.adam(1e-3))
    b.build(ModelSpec(params), _spec())
    assert "adafactor" in b.explain()


def test_choose_optimizer_picks_by_exact_fit():
    from autodist_tpu.strategy.auto_strategy import choose_optimizer

    params = {"emb": np.zeros((4096, 256), np.float32)}  # 4 MiB
    tight = choose_optimizer(params, memory_budget_bytes=10 << 20)
    roomy = choose_optimizer(params, memory_budget_bytes=64 << 20)
    assert tight.factored and not roomy.factored
    # The chosen optimizers are usable as-is.
    for choice in (tight, roomy):
        state = choice.optimizer.init({"w": jnp.zeros((4, 4))})
        assert state is not None
    assert "exceeds budget" in tight.reason and "<= budget" in roomy.reason


def test_partition_log_prints_exact_bytes(caplog):
    """Threshold comparisons print real byte counts (no '0 MiB >= 0 MiB' at
    scaled-down thresholds)."""
    params = {"big": np.zeros((4096, 64), np.float32)}  # 1 MiB
    b = AutoStrategy(memory_budget_bytes=1 << 30,
                     partition_threshold_bytes=256 << 10)
    b.build(ModelSpec(params), _spec())
    text = b.explain()
    assert "1.00 MiB >= partition threshold 256 KiB" in text, text


def test_explain_has_regime_and_per_param_rows():
    builder = AutoStrategy()
    builder.build(ModelSpec(_dense_params(n=2)), _spec())
    text = builder.explain()
    assert "<regime>" in text and "AllReduce" in text
    assert "w0" in text and "w1" in text
