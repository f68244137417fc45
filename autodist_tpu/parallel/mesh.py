"""Device-mesh construction from a ResourceSpec.

The reference reified "where replicas live" as a list of device strings inside the
strategy (``strategy.proto:62-68``) resolved to TF device names
(``kernel/device/resolver.py:38-67``). The TPU-native design replaces both with a named
:class:`jax.sharding.Mesh`: data-parallel replicas are coordinates along the ``data``
axis, PS/weight-update sharding lives on ``reduce``, variable partitioning on ``model``,
sequence/context parallelism on ``seq``, expert parallelism on ``expert``, pipeline
stages on ``pipe``. Collectives ride ICI within a slice and DCN across slices; XLA
inserts them from shardings.
"""

import collections
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from autodist_tpu import const
from autodist_tpu.utils import logging

# Canonical axis order. Axes the user does not size default to 1 so that any
# PartitionSpec naming them is always valid.
STANDARD_AXES = (
    const.MESH_AXIS_DATA,
    const.MESH_AXIS_REDUCE,
    const.MESH_AXIS_MODEL,
    const.MESH_AXIS_SEQ,
    const.MESH_AXIS_EXPERT,
    const.MESH_AXIS_PIPE,
)


def standard_mesh_shape(n_devices: int, axes: Optional[Dict[str, int]] = None) -> "collections.OrderedDict":
    """Resolve a possibly-partial axis-size dict into a full OrderedDict over STANDARD_AXES.

    A value of ``-1`` (or an unspecified ``data`` axis) absorbs the remaining devices.
    Raises if the product does not match ``n_devices``.
    """
    axes = dict(axes or {})
    unknown = set(axes) - set(STANDARD_AXES)
    if unknown:
        raise ValueError(f"Unknown mesh axes {sorted(unknown)}; valid: {STANDARD_AXES}")

    shape = collections.OrderedDict((a, int(axes.get(a, 1))) for a in STANDARD_AXES)
    if const.MESH_AXIS_DATA not in axes:
        shape[const.MESH_AXIS_DATA] = -1
    bad = {a: s for a, s in shape.items() if s != -1 and s < 1}
    if bad:
        raise ValueError(f"Mesh axis sizes must be >= 1 (or -1 to fill), got {bad}")

    fill_axes = [a for a, s in shape.items() if s == -1]
    if len(fill_axes) > 1:
        raise ValueError(f"At most one -1 axis allowed, got {fill_axes}")
    fixed = int(np.prod([s for s in shape.values() if s != -1]))
    if fill_axes:
        if n_devices % fixed != 0:
            raise ValueError(
                f"Cannot fill axis {fill_axes[0]}: {n_devices} devices not divisible by {fixed}")
        shape[fill_axes[0]] = n_devices // fixed
    elif fixed != n_devices:
        raise ValueError(f"Mesh axes {dict(shape)} require {fixed} devices, have {n_devices}")
    return shape


def build_mesh(resource_spec=None, axes: Optional[Dict[str, int]] = None,
               devices: Optional[Sequence] = None) -> Mesh:
    """Build the global device mesh.

    ``axes`` overrides the ResourceSpec's ``mesh:`` section. ``devices`` defaults to all
    global JAX devices (multi-host: every process passes the same global list, standard
    SPMD). Uses :func:`mesh_utils.create_device_mesh` on TPU so the mesh layout follows
    the physical ICI topology (a shape it cannot lay out raises); a plain reshape on the
    CPU test mesh.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if axes is None and resource_spec is not None:
        axes = resource_spec.mesh_config
    shape = standard_mesh_shape(len(devices), axes)
    dims = tuple(shape.values())

    platform = devices[0].platform
    if platform == "tpu":
        dev_array = mesh_utils.create_device_mesh(dims, devices=devices)
    else:
        dev_array = np.asarray(devices).reshape(dims)

    mesh = Mesh(dev_array, tuple(shape.keys()))
    logging.debug("Built mesh %s over %d %s device(s)", dict(shape), len(devices), platform)
    return mesh


def ambient_mesh():
    """The mesh in effect at trace time, or None: the abstract-mesh context if
    set (inside ``shard_map``, where it marks the manual axes), else the
    ``with mesh:`` physical-mesh context the runner steps under."""
    abstract = jax.sharding.get_abstract_mesh()
    if not abstract.empty:
        return abstract
    # No public accessor for the `with mesh:` context.
    from jax._src import mesh as mesh_lib
    physical = mesh_lib.thread_resources.env.physical_mesh
    return None if physical.empty else physical


def per_device(fn, args: Sequence, batched: Sequence[bool]):
    """``fn(*args)`` run once per device of the ambient mesh, for bodies the
    compiler cannot partition itself — a Mosaic kernel under a mesh of
    several devices is refused ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map").

    ``batched[i]`` marks the args whose leading dim is the batch: it is split
    over the data-parallel axes when they divide it, as are the outputs'
    leading dims; every other arg arrives whole on each device (a
    model-sharded table is gathered) and its gradient is summed across
    devices by the transpose. With one device, or inside a caller's
    ``shard_map`` that already made every axis manual, ``fn`` is called
    directly."""
    from jax.sharding import PartitionSpec as P

    from autodist_tpu.parallel.plan import DP_AXES

    mesh = ambient_mesh()
    manual = set(getattr(mesh, "manual_axes", ()))
    auto = [a for a in getattr(mesh, "axis_names", ())
            if a not in manual and mesh.shape[a] > 1]
    if not auto:
        return fn(*args)
    dp, n_dp = _sized(mesh, tuple(a for a in DP_AXES if a in auto))
    rows = [x.shape[0] for x, b in zip(args, batched) if b]
    split = dp if n_dp > 1 and all(n % n_dp == 0 for n in rows) else None
    fn, specs = _stored_operands(fn, args, batched, split, dp if n_dp > 1 else ())
    return jax.shard_map(
        fn, mesh=mesh, axis_names=frozenset(mesh.axis_names) - manual,
        in_specs=specs,
        out_specs=P(split), check_vma=False)(*args)


# What follows stands BELOW ``per_device`` on purpose, imports and all: a
# Mosaic kernel's serialized module carries the line numbers of the frames it
# was called through, ``per_device``'s among them, so a line added above it
# changes every cell's compiled step (and its compile-cache key) for nothing.

import contextlib  # noqa: E402
import contextvars  # noqa: E402

# ``shape -> tensor axis`` of the leaves stored as shares over the data axes
# (``ShardingPlan.data_shard_axes``), while a step that stores its state so is
# traced; None otherwise.
_STORED_SHARDS = contextvars.ContextVar("stored_shards", default=None)


@contextlib.contextmanager
def stored_shards(axes: Optional[Dict[tuple, int]]):
    """While tracing inside it, :func:`per_device` hands a non-batched operand
    of a shape in ``axes`` to the device as the share it stores (split along
    ``axes[shape]`` over the data axes) and gathers it in the body, so that
    its gradient leaves the body reduce-scattered onto the shares and not
    all-reduced whole. ``axes`` None or empty: nothing changes."""
    token = _STORED_SHARDS.set(axes or None)
    try:
        yield
    finally:
        _STORED_SHARDS.reset(token)


def stored_axis(shape) -> Optional[int]:
    """The tensor axis along which a leaf of ``shape`` arrives at a
    :func:`per_device` body as its share, inside :func:`stored_shards`; None
    where it arrives whole (no such context, or no such leaf). For a caller
    that would rather cast a leaf before it is gathered than after."""
    return (_STORED_SHARDS.get() or {}).get(tuple(shape))


def _sized(mesh, axes):
    """``(axes, the product of their sizes)``."""
    return axes, int(np.prod([mesh.shape[a] for a in axes]))


def _stored_operands(fn, args: Sequence, batched: Sequence[bool], split, dp):
    """``(the body, its in_specs)`` of :func:`per_device`'s ``shard_map``:
    ``fn`` itself on operands that arrive split over the batch or whole, or,
    inside :func:`stored_shards`, a body that first gathers along its stored
    axis every operand that arrived as its share."""
    from jax.sharding import PartitionSpec as P

    stored = (_STORED_SHARDS.get() or {}) if dp else {}
    # tensor axis along which operand i is stored as shares, or None
    shares = [None if b else stored.get(getattr(x, "shape", None))
              for x, b in zip(args, batched)]
    specs = tuple(P(split) if b else
                  P() if axis is None else P(*([None] * axis), dp)
                  for b, axis in zip(batched, shares))
    if all(axis is None for axis in shares):
        return fn, specs

    def body(*local):
        return fn(*(x if axis is None else
                    jax.lax.all_gather(x, dp, axis=axis, tiled=True)
                    for x, axis in zip(local, shares)))
    return body, specs


def constrain_batch(x):
    """``x`` held to the batch sharding (its leading dim split over the
    data-parallel axes of the ambient mesh) where they divide it; ``x`` itself
    with no mesh, one device, or inside a ``shard_map``. A model calls it at
    its layers' edges: with parameters stored as shares over the data axis
    (``strategy.FullySharded``) the partitioner, left to itself, may reshard
    the activations (an all-to-all a product) where it should gather the
    weights."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from autodist_tpu.parallel.plan import DP_AXES

    mesh = ambient_mesh()
    if mesh is None or getattr(mesh, "manual_axes", ()):
        return x
    dp, n_dp = _sized(mesh, tuple(a for a in DP_AXES if a in mesh.axis_names
                                  and mesh.shape[a] > 1))
    if n_dp == 1 or x.shape[0] % n_dp:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(dp)))


def data_axis_size(mesh: Mesh) -> int:
    return mesh.shape[const.MESH_AXIS_DATA]


def single_device_mesh() -> Mesh:
    """A 1-device mesh (used to run the original single-node step for parity checks)."""
    return build_mesh(devices=[jax.devices()[0]], axes={const.MESH_AXIS_DATA: 1})
