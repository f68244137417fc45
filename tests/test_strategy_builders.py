"""The 8 strategy builders — policy parity with reference autodist/strategy/*."""

import jax.numpy as jnp
import pytest

from autodist_tpu.model_spec import ModelSpec
from autodist_tpu.proto import strategy_pb2
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy import (AllReduce, Parallax, PartitionedAR, PartitionedPS,
                                   PS, PSLoadBalancing, RandomAxisPartitionAR,
                                   UnevenPartitionedPS, byte_size_load_fn)
from autodist_tpu.strategy.partition_utils import (smallest_divisor_at_least_2,
                                                   smallest_non_divisor_at_least_2)

RES = ResourceSpec("nodes: [{address: localhost, tpus: 8}]")
RES_REDUCE4 = ResourceSpec("{nodes: [{address: localhost, tpus: 8}], mesh: {reduce: 4, data: 2}}")


def _model(sparse=False):
    params = {
        "emb": jnp.zeros((12, 4)),     # 48 floats
        "w1": jnp.zeros((7, 3)),       # 21 floats, dim0 prime
        "w2": jnp.zeros((4, 4)),       # 16 floats
        "b": jnp.zeros((3,)),          # 3 floats
        "s": jnp.zeros(()),            # scalar
    }
    return ModelSpec(params, sparse_names=["emb"] if sparse else [])


def test_ps_all_vars_single_destination():
    s = PS().build(_model(), RES)
    assert len(s.node_config) == 5
    for n in s.node_config:
        assert n.WhichOneof("synchronizer") == "ps_synchronizer"
        assert n.ps_synchronizer.reduction_destination == "reduce:0"
        assert n.ps_synchronizer.sync
    # PS defaults to full weight-update sharding
    assert s.mesh_axes()["reduce"] == 8


def test_ps_lb_greedy_balance():
    s = PSLoadBalancing().build(_model(), RES_REDUCE4)
    dests = {n.var_name: n.ps_synchronizer.reduction_destination for n in s.node_config}
    # largest param (emb) goes to the first empty shard; the rest balance greedily
    assert len(set(dests.values())) == 4
    loads = {}
    model = _model()
    for name, d in dests.items():
        loads[d] = loads.get(d, 0) + byte_size_load_fn(model[name])
    # max load <= emb alone + smallest (greedy bound for this tiny instance)
    assert max(loads.values()) == byte_size_load_fn(model["emb"])


def test_partitioned_ps_shard_counts():
    s = PartitionedPS().build(_model(), RES_REDUCE4)
    nodes = {n.var_name: n for n in s.node_config}
    # emb dim0=12 -> smallest divisor 2
    assert list(nodes["emb"].partitioner.num_shards) == [2, 1]
    assert len(nodes["emb"].part_config) == 2
    assert nodes["emb"].part_config[0].var_name == "emb/part_0"
    # w1 dim0=7 prime -> divisor 7 = dim0 itself
    assert list(nodes["w1"].partitioner.num_shards) == [7, 1]
    # scalar s and b(dim0=3... prime=3 <= cap) get partitioned or fall back
    assert not nodes["s"].HasField("partitioner")


def test_uneven_partitioned_ps_non_divisor():
    s = UnevenPartitionedPS().build(_model(), RES_REDUCE4)
    nodes = {n.var_name: n for n in s.node_config}
    # emb dim0=12: smallest non-divisor >= 2 is 5
    assert list(nodes["emb"].partitioner.num_shards) == [5, 1]
    # w1 dim0=7: smallest non-divisor is 2
    assert list(nodes["w1"].partitioner.num_shards) == [2, 1]


def test_all_reduce_groups_and_compressor():
    s = AllReduce(chunk_size=2, compressor="HorovodCompressor").build(_model(), RES)
    groups = [n.all_reduce_synchronizer.group for n in s.node_config]
    assert groups == [0, 0, 1, 1, 2]
    for n in s.node_config:
        assert n.all_reduce_synchronizer.compressor == strategy_pb2.AllReduceSynchronizer.BF16
    assert s.mesh_axes()["data"] == 8


def test_all_reduce_rejects_bad_args():
    with pytest.raises(ValueError):
        AllReduce(chunk_size=0)
    with pytest.raises(ValueError):
        AllReduce(compressor="zip")
    with pytest.raises(ValueError):
        AllReduce(all_reduce_spec="banana")


def test_partitioned_ar_running_group_counter():
    s = PartitionedAR(chunk_size=3).build(_model(), RES)
    shards = []
    for n in s.node_config:
        if n.HasField("partitioner"):
            shards.extend(p.all_reduce_synchronizer.group for p in n.part_config)
        else:
            shards.append(n.all_reduce_synchronizer.group)
    # groups increase every chunk_size shards
    assert shards == sorted(shards)
    assert shards[0] == 0 and shards[-1] == (len(shards) - 1) // 3


def test_random_axis_deterministic_and_sparse_axis0():
    s1 = RandomAxisPartitionAR(seed=7).build(_model(sparse=True), RES)
    s2 = RandomAxisPartitionAR(seed=7).build(_model(sparse=True), RES)
    assert s1.proto.node_config == s2.proto.node_config
    nodes = {n.var_name: n for n in s1.node_config}
    if nodes["emb"].HasField("partitioner"):
        ns = list(nodes["emb"].partitioner.num_shards)
        assert ns[0] > 1 and all(x == 1 for x in ns[1:])  # sparse forced to axis 0


def test_parallax_routes_sparse_to_ps():
    s = Parallax().build(_model(sparse=True), RES)
    nodes = {n.var_name: n for n in s.node_config}
    assert nodes["emb"].WhichOneof("synchronizer") == "ps_synchronizer"
    assert nodes["w1"].WhichOneof("synchronizer") == "all_reduce_synchronizer"
    assert nodes["emb"].sparse


def test_divisor_helpers():
    assert smallest_divisor_at_least_2(12) == 2
    assert smallest_divisor_at_least_2(7) == 7
    assert smallest_divisor_at_least_2(9) == 3
    assert smallest_divisor_at_least_2(1) is None
    assert smallest_divisor_at_least_2(7, cap=5) is None
    assert smallest_non_divisor_at_least_2(12) == 5
    assert smallest_non_divisor_at_least_2(7) == 2
    assert smallest_non_divisor_at_least_2(1) is None


def test_fully_sharded_partitions_large_leaves_over_the_data_axis():
    """Beyond the reference's eight: every leaf of ``MIN_SHARDED_SIZE``
    elements or more gets a partitioner of ``dp`` shares on the first axis ``dp`` divides,
    mapped onto the ``data`` mesh axis, an AllReduce synchronizer a share;
    smaller leaves (and ones no axis of which ``dp`` divides) stay whole."""
    from autodist_tpu.parallel.plan import ShardingPlan
    from autodist_tpu.strategy import FullySharded
    from jax.sharding import PartitionSpec as P

    res = ResourceSpec("{nodes: [{address: localhost, tpus: 4}], mesh: {data: 4}}")
    model = ModelSpec({
        "emb": jnp.zeros((1024, 512)),
        "w1": jnp.zeros((513, 513)),      # large, and 4 divides no axis
        "w2": jnp.zeros((512, 512)),      # MIN_SHARDED_SIZE exactly
        "b": jnp.zeros((512,)),
        "s": jnp.zeros(()),
    })
    s = FullySharded().build(model, res)
    assert s.mesh_axes()["data"] == 4 and s.mesh_axes()["model"] == 1
    nodes = {n.var_name: n for n in s.node_config}
    assert list(nodes["emb"].partitioner.num_shards) == [4, 1]
    assert nodes["emb"].partitioner.mesh_axis == "data"
    assert [p.var_name for p in nodes["emb"].part_config] == [
        f"emb/part_{k}" for k in range(4)]
    assert all(p.WhichOneof("synchronizer") == "all_reduce_synchronizer"
               for p in nodes["emb"].part_config)
    assert list(nodes["w2"].partitioner.num_shards) == [4, 1]
    for whole in ("w1", "b", "s"):
        assert not nodes[whole].HasField("partitioner")
        assert nodes[whole].WhichOneof("synchronizer") == "all_reduce_synchronizer"
    plan = ShardingPlan.from_strategy(s, model)
    assert plan.params["emb"].pspec == plan.params["emb"].opt_pspec == P("data", None)
    assert plan.params["w1"].pspec == P()
    assert set(plan.data_sharded) == {"emb", "w2"}
    # the reference-parity toy is all below the threshold: whole
    assert not ShardingPlan.from_strategy(
        FullySharded().build(_model(), res), _model()).data_sharded
