"""Shard-count policies shared by the partitioned strategy builders.

Ports of the reference's pure algorithms: smallest divisor of dim0 for even
partitioning (``partitioned_ps_strategy.py:125-135``) and smallest *non*-divisor for
the uneven variant (``uneven_partition_ps_strategy.py:125-135``), which deliberately
exercises remainder handling (on TPU: pad-and-mask shards).
"""

from typing import Optional, Sequence, Tuple

import numpy as np

from autodist_tpu.model_spec import ParamSpec

# Elements from which ``FullySharded`` stores a leaf as shares (1 MiB of
# float32): matrices are above it, norms, biases and a state-space layer's
# ``[E, N]`` decay below.
MIN_SHARDED_SIZE = 1 << 18


def smallest_divisor_at_least_2(n: int, cap: Optional[int] = None) -> Optional[int]:
    """Smallest k >= 2 dividing n (None if n < 2 or no divisor <= cap)."""
    if n < 2:
        return None
    k = 2
    while k * k <= n:
        if n % k == 0:
            break
        k += 1
    else:
        k = n  # n is prime: its smallest divisor >= 2 is itself
    if cap is not None and k > cap:
        return None
    return k


def smallest_non_divisor_at_least_2(n: int, cap: Optional[int] = None) -> Optional[int]:
    """Smallest k >= 2 NOT dividing n (None if n < 2 or k exceeds cap)."""
    if n < 2:
        return None
    k = 2
    while n % k == 0:
        k += 1
    if cap is not None and k > cap:
        return None
    return k


def partitionable_axis(spec: ParamSpec) -> Optional[int]:
    """The tensor axis eligible for partitioning, or None.

    Like the reference (one active axis, ``kernel/partitioner.py:51-70``), axis 0 is
    the default; sparse (embedding) parameters must partition axis 0 so row updates
    stay shard-local (reference forced axis 0 for sparse,
    ``random_axis_partition_all_reduce_strategy.py:118-141``).
    """
    if not spec.shape or spec.shape[0] < 2:
        return None
    return 0


def make_num_shards(rank: int, axis: int, k: int) -> Tuple[int, ...]:
    """Per-axis shard counts with one active axis (reference partitioner str "k,1,..")."""
    return tuple(k if i == axis else 1 for i in range(max(rank, 1)))


def data_shard_axis(shape: Sequence[int], dp: int) -> Optional[int]:
    """The tensor axis along which a leaf of ``shape`` is stored as ``dp``
    shares over the data axis, or None where it stays whole: the first axis
    that ``dp`` divides, for a leaf of ``MIN_SHARDED_SIZE`` elements or more. A
    function of the shape alone, so that whoever lays parameters out before
    the plan exists (a caller that cannot hold them whole) lays them out as
    the plan will."""
    if dp <= 1 or not shape or int(np.prod(shape)) < MIN_SHARDED_SIZE:
        return None
    return next((axis for axis, dim in enumerate(shape)
                 if dim > 0 and dim % dp == 0), None)
