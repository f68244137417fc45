"""Fully sharded data parallelism: the training state stored as shares over
the ``data`` axis.

Beyond reference parity (the reference's partitioned strategies shard a
variable over parameter servers, one replica's batch each;
``PartitionedPS`` maps that onto the ``model`` axis, where every device runs
the whole batch, and ``ShardingPlan.with_zero_update`` shards the optimizer's
moments and leaves parameters and gradients whole). Here every parameter of
``MIN_SHARDED_SIZE`` elements or more is partitioned over the ``data`` axis itself
(:func:`~autodist_tpu.strategy.partition_utils.data_shard_axis` names the
tensor axis), with an AllReduce synchronizer a share: the plan stores the
leaf, its gradient and the optimizer's moments as ``1 / dp`` a device, the
batch is split over the same axis, and the compiled step gathers a weight
where a layer uses it, reduce-scatters its gradient and updates the share
(ZeRO stage 3, arXiv 1910.02054; FSDP). Smaller leaves (norms, biases) stay
whole and all-reduced. It is the layout for a model whose state does not fit
one device: 16 bytes a parameter become ``16 / dp``.

The model keeps the batch sharding of its activations at its layers' edges
(:func:`autodist_tpu.parallel.mesh.constrain_batch`): left to itself the
partitioner may move activations instead of weights.
"""

from autodist_tpu import const
from autodist_tpu.model_spec import ModelSpec
from autodist_tpu.proto import strategy_pb2
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy.all_reduce_strategy import fill_ar_synchronizer
from autodist_tpu.strategy.base import AR_DEFAULT_AXES, Strategy, StrategyBuilder
from autodist_tpu.strategy.partition_utils import (data_shard_axis,
                                                   make_num_shards)

_CHUNK_SIZE = 128                     # ``AllReduce``'s default fusion groups


class FullySharded(StrategyBuilder):
    def build(self, model_spec: ModelSpec, resource_spec: ResourceSpec) -> Strategy:
        strategy = Strategy()
        axes = self._resolved_axes(resource_spec, AR_DEFAULT_AXES)
        dp = axes[const.MESH_AXIS_DATA]
        for i, spec in enumerate(model_spec.trainable.values()):
            node = strategy.proto.node_config.add(var_name=spec.name)
            node.sparse = spec.sparse
            fill = dict(spec=strategy_pb2.AllReduceSynchronizer.AUTO,
                        compressor=strategy_pb2.AllReduceSynchronizer.NONE,
                        group=i // _CHUNK_SIZE)
            axis = data_shard_axis(spec.shape, dp)
            if axis is None:
                fill_ar_synchronizer(node, **fill)
                continue
            node.partitioner.num_shards.extend(
                make_num_shards(len(spec.shape), axis, dp))
            node.partitioner.mesh_axis = const.MESH_AXIS_DATA
            for k in range(dp):
                part = node.part_config.add(var_name=f"{spec.name}/part_{k}")
                part.sparse = spec.sparse
                fill_ar_synchronizer(part, **fill)
        self._fill_mesh_config(strategy, resource_spec, axes)
        return strategy
