"""Strategy builders — the "compiler frontend".

Eight builders with the same distribution policies as the reference
(``autodist/strategy/*``), operating on (ModelSpec, ResourceSpec) and emitting a
serializable Strategy proto. The policies are pure placement/synchronization
algorithms and port at the algorithm level; what changes is the target: node configs
compile into mesh shardings instead of TF device strings.
"""

from autodist_tpu.strategy.base import Strategy, StrategyBuilder, StrategyCompiler
from autodist_tpu.strategy.ps_strategy import PS
from autodist_tpu.strategy.ps_lb_strategy import PSLoadBalancing, byte_size_load_fn
from autodist_tpu.strategy.partitioned_ps_strategy import PartitionedPS
from autodist_tpu.strategy.uneven_partition_ps_strategy import UnevenPartitionedPS
from autodist_tpu.strategy.all_reduce_strategy import AllReduce
from autodist_tpu.strategy.partitioned_all_reduce_strategy import PartitionedAR
from autodist_tpu.strategy.fully_sharded_strategy import FullySharded
from autodist_tpu.strategy.random_axis_partition_all_reduce_strategy import RandomAxisPartitionAR
from autodist_tpu.strategy.parallax_strategy import Parallax
from autodist_tpu.strategy.expert_parallel_strategy import ExpertParallel
from autodist_tpu.strategy.pipeline_strategy import Pipeline
from autodist_tpu.strategy.sequence_parallel_strategy import SequenceParallel
from autodist_tpu.strategy.auto_strategy import AutoStrategy
from autodist_tpu.strategy.tuner import (CandidateResult, TuneResult,
                                         measure_candidate, tune_strategy)
from autodist_tpu.strategy.autotune import (Candidate, TunedPlan, autotune,
                                            enumerate_candidates,
                                            plan_cache_key)

__all__ = [
    "Strategy", "StrategyBuilder", "StrategyCompiler",
    "PS", "PSLoadBalancing", "byte_size_load_fn", "PartitionedPS",
    "UnevenPartitionedPS", "AllReduce", "PartitionedAR", "FullySharded",
    "RandomAxisPartitionAR", "Parallax", "ExpertParallel", "Pipeline",
    "SequenceParallel", "AutoStrategy", "tune_strategy", "TuneResult",
    "measure_candidate", "CandidateResult",
    "autotune", "TunedPlan", "Candidate", "enumerate_candidates",
    "plan_cache_key",
]
