"""Sharding plan — the compiled, executable form of a Strategy.

This is the TPU-native counterpart of the reference's GraphTransformer pipeline
(``kernel/graph_transformer.py:55-92``): where the reference materialized a strategy
by rewriting the graph (Partitioner -> Replicator -> Synchronizers), we compile it
into per-parameter ``PartitionSpec``s plus synchronization metadata, and let the XLA
SPMD partitioner insert the collectives:

- AllReduce synchronizer  -> parameter replicated; the gradient cross-replica sum is
  the implicit psum in the backward pass (reference ``all_reduce_synchronizer.py``).
- PS synchronizer         -> weight-update sharding: optimizer state (and the update
  computation) sharded along the ``reduce`` axis; XLA lowers the grad flow into
  reduce-scatter + local update + all-gather (reference PS push/pull + accumulators,
  ``ps_synchronizer.py:556-633``).
- Partitioner             -> the parameter itself is stored sharded on the ``model``
  axis (reference ``kernel/partitioner.py`` rebuilt vars as PartitionedVariables).
"""

import collections
import dataclasses
from typing import Any, Dict, Optional, Tuple

import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from autodist_tpu import const
from autodist_tpu.model_spec import ModelSpec
from autodist_tpu.proto import strategy_pb2

# Data-parallel axes: the batch dimension shards over both; with PS strategies the
# reduce axis doubles as the weight-update sharding axis (every device is a data
# replica AND a parameter shard).
DP_AXES = (const.MESH_AXIS_DATA, const.MESH_AXIS_REDUCE)

SYNC_ALLREDUCE = "allreduce"
SYNC_PS = "ps"

COMP_NONE = strategy_pb2.AllReduceSynchronizer.NONE
COMP_BF16 = strategy_pb2.AllReduceSynchronizer.BF16
COMP_BF16_EF = strategy_pb2.AllReduceSynchronizer.BF16_EF
COMP_POWER_SGD = strategy_pb2.AllReduceSynchronizer.POWER_SGD


@dataclasses.dataclass(frozen=True)
class ParamPlan:
    """Compiled distribution of one parameter."""

    name: str
    pspec: P                      # parameter storage sharding
    opt_pspec: P                  # optimizer-state sharding (ZeRO shard for PS family)
    sync: str                     # SYNC_ALLREDUCE | SYNC_PS
    compressor: int = COMP_NONE   # strategy_pb2.AllReduceSynchronizer.Compressor
    power_sgd_rank: int = 1       # approximation rank when compressor == POWER_SGD
    group: int = 0                # collective fusion group (bucketing)
    spec: int = 0                 # network tier: AUTO | ICI | DCN (hierarchical)
    sparse: bool = False
    staleness: int = 0
    synchronous: bool = True
    partition_axis: Optional[int] = None   # tensor axis sharded on a mesh axis
    num_shards: Tuple[int, ...] = ()       # logical shard counts from the strategy
    # Mesh axis the partition maps onto: "model" for tensor parallelism, "expert"
    # for expert parallelism (PartitionConfig.mesh_axis).
    partition_mesh_axis: str = const.MESH_AXIS_MODEL
    # Uneven partitioning (reference kernel/partitioner.py:660-704 sliced remainders;
    # XLA shardings need even tiles, so storage is zero-padded to padded_dim along
    # partition_axis and sliced back to logical_dim around the user's loss fn).
    padded_dim: Optional[int] = None
    logical_dim: Optional[int] = None
    # Batch-leaf name providing this sparse param's gather indices (model_spec jaxpr
    # provenance): enables the (indices, rows) wire format for gradient sync.
    index_leaf: Optional[str] = None
    # Logical parameter shape (model_spec metadata): lets plan-level transforms
    # (ZeRO opt-state sharding) reason about tiling without a live tree.
    shape: Tuple[int, ...] = ()


class ShardingPlan:
    """Per-parameter plans + mesh shape, derived from a compiled Strategy."""

    # ZeRO-style weight-update sharding (arXiv 2004.13336) off by default;
    # :meth:`with_zero_update` returns a plan with it on. An instance
    # attribute on derived plans, a class default here so pre-existing
    # pickles/constructions keep working.
    zero = False

    def __init__(self, mesh_axes: "collections.OrderedDict[str, int]",
                 params: Dict[str, ParamPlan]):
        self.mesh_axes = mesh_axes
        self.params = params

    # ------------------------------------------------------------------ build
    @classmethod
    def from_strategy(cls, strategy, model_spec: ModelSpec) -> "ShardingPlan":
        """The plan of a compiled strategy. Once a build: its seconds are
        booked as ``setup.plan_build_s`` whether or not telemetry is on."""
        from autodist_tpu import telemetry
        with telemetry.phase("setup.plan_build_s"):
            return cls._from_strategy(strategy, model_spec)

    @classmethod
    def _from_strategy(cls, strategy, model_spec: ModelSpec) -> "ShardingPlan":
        mesh_axes = collections.OrderedDict(
            (a.name, a.size) for a in strategy.mesh_config.axes)

        nodes = {n.var_name: n for n in strategy.node_config}
        plans: Dict[str, ParamPlan] = {}
        for name, pspec_meta in model_spec.params.items():
            if not pspec_meta.trainable:
                plans[name] = ParamPlan(name=name, pspec=P(), opt_pspec=P(),
                                        sync=SYNC_ALLREDUCE,
                                        shape=tuple(pspec_meta.shape))
                continue
            node = nodes.get(name)
            plans[name] = cls._plan_for(node, pspec_meta, mesh_axes)
        placement_only = [p.name for p in plans.values()
                          if p.partition_axis is not None and p.pspec == P()]
        if placement_only:
            from autodist_tpu.utils import logging
            logging.warning(
                "Partitioning for %d parameter(s) is placement-only (the mesh has "
                "no matching partition axis > 1, so storage stays replicated): %s",
                len(placement_only), ", ".join(sorted(placement_only)[:8]))
        return cls(mesh_axes, plans)

    @staticmethod
    def _plan_for(node, meta, mesh_axes) -> ParamPlan:
        reduce_size = mesh_axes.get(const.MESH_AXIS_REDUCE, 1)
        if node is None:
            # No config for this param: replicate + implicit psum (safe default).
            return ParamPlan(name=meta.name, pspec=P(), opt_pspec=P(),
                             sync=SYNC_ALLREDUCE, sparse=meta.sparse,
                             index_leaf=meta.index_leaf,
                             shape=tuple(meta.shape))

        partition_axis = None
        num_shards: Tuple[int, ...] = ()
        param_pspec = P()
        partition_mesh_axis = const.MESH_AXIS_MODEL
        if node.HasField("partitioner"):
            num_shards = tuple(node.partitioner.num_shards)
            active = [i for i, k in enumerate(num_shards) if k > 1]
            if active:
                partition_axis = active[0]
            if node.partitioner.mesh_axis:
                partition_mesh_axis = node.partitioner.mesh_axis

        # Physical storage sharding: put the target mesh axis ("model" for tensor
        # parallelism, "expert" for expert parallelism) on the partitioned tensor
        # axis when the mesh has one. Dimensions that don't tile evenly get padded
        # storage: zero-pad to the next multiple of the axis size and slice back to
        # the logical shape around the user's computation (the TPU-native form of
        # the reference's remainder slicing, kernel/partitioner.py:660-704).
        axis_size = mesh_axes.get(partition_mesh_axis, 1)
        padded_dim = logical_dim = None
        if partition_axis is not None and axis_size > 1:
            spec_dims: list = [None] * len(meta.shape)
            spec_dims[partition_axis] = partition_mesh_axis
            param_pspec = P(*spec_dims)
            dim = meta.shape[partition_axis]
            if dim % axis_size != 0:
                logical_dim = dim
                padded_dim = -(-dim // axis_size) * axis_size

        kind = node.WhichOneof("synchronizer")
        if kind is None and node.part_config:
            # Partitioned node: children carry the synchronizer; they are homogeneous
            # by construction, so inspect the first.
            kind = node.part_config[0].WhichOneof("synchronizer")
            sync_node = node.part_config[0]
        else:
            sync_node = node

        if kind == "ps_synchronizer":
            ps = sync_node.ps_synchronizer
            opt_pspec = _zero_style_opt_pspec(meta, param_pspec, reduce_size)
            return ParamPlan(name=meta.name, pspec=param_pspec, opt_pspec=opt_pspec,
                             sync=SYNC_PS, sparse=meta.sparse or node.sparse,
                             staleness=ps.staleness, synchronous=ps.sync,
                             partition_axis=partition_axis, num_shards=num_shards,
                             partition_mesh_axis=partition_mesh_axis,
                             padded_dim=padded_dim, logical_dim=logical_dim,
                             index_leaf=meta.index_leaf,
                             shape=tuple(meta.shape))

        ar = sync_node.all_reduce_synchronizer
        return ParamPlan(name=meta.name, pspec=param_pspec, opt_pspec=param_pspec,
                         sync=SYNC_ALLREDUCE, compressor=ar.compressor,
                         power_sgd_rank=max(1, ar.power_sgd_rank), group=ar.group,
                         spec=ar.spec,
                         sparse=meta.sparse or node.sparse,
                         partition_axis=partition_axis, num_shards=num_shards,
                         partition_mesh_axis=partition_mesh_axis,
                         padded_dim=padded_dim, logical_dim=logical_dim,
                         index_leaf=meta.index_leaf,
                         shape=tuple(meta.shape))

    # -------------------------------------------------------------- accessors
    @property
    def dp_size(self) -> int:
        return (self.mesh_axes.get(const.MESH_AXIS_DATA, 1)
                * self.mesh_axes.get(const.MESH_AXIS_REDUCE, 1))

    @property
    def has_compression(self) -> bool:
        return any(p.compressor != COMP_NONE for p in self.params.values())

    @property
    def is_async(self) -> bool:
        """True when any PS node requests a non-synchronous regime (sync=False or
        staleness>0) — these compile to the host-driven dispatch loop
        (parallel/staleness.py), not to one SPMD program."""
        return any(p.sync == SYNC_PS and (not p.synchronous or p.staleness > 0)
                   for p in self.params.values())

    @property
    def max_staleness(self) -> int:
        return max((p.staleness for p in self.params.values()), default=0)

    @property
    def all_params_replicated(self) -> bool:
        return all(p.pspec == P() for p in self.params.values())

    @property
    def data_sharded(self) -> Dict[str, ParamPlan]:
        """The parameters stored as shares over a data-parallel axis
        (``strategy.FullySharded``): every device is a data replica AND holds
        ``1 / dp`` of the leaf, its gradient and its optimizer state."""
        return {n: p for n, p in self.params.items()
                if any(axis in DP_AXES for axis in _spec_axes(p.pspec))}

    @property
    def update_sharded(self) -> bool:
        """The step constrains gradients, updates, optimizer state and
        parameters to the plan's specs (the runner's ZeRO points): under
        :meth:`with_zero_update`, and where parameters are themselves stored
        as shares over a data axis. There ``opt_pspec`` is the parameter's own
        spec, so the gradient is reduce-scattered onto the share, the update
        runs on it and nothing is gathered at the end."""
        return bool(self.zero or self.data_sharded)

    def data_shard_axes(self) -> Dict[Tuple[int, ...], int]:
        """``shape -> tensor axis`` of the data-sharded parameters: what
        :func:`autodist_tpu.parallel.mesh.stored_shards` needs to hand a
        kernel such a leaf as its share and gather it there."""
        return {p.shape: p.partition_axis for p in self.data_sharded.values()}

    def data_shard_bytes(self, model_spec: ModelSpec, dp: int) -> int:
        """Bytes a device receives when every data-sharded parameter is
        gathered once (``(dp - 1) / dp`` of each leaf), which is also what
        it sends when each gradient is reduce-scattered once."""
        full = sum(model_spec.params[n].byte_size for n in self.data_sharded)
        return full * (dp - 1) // dp if dp > 1 else 0

    @property
    def has_padding(self) -> bool:
        """True when any parameter uses padded storage (uneven partitioning)."""
        return any(p.padded_dim is not None for p in self.params.values())

    @property
    def sparse_wire_params(self) -> Dict[str, ParamPlan]:
        """Sparse params eligible for the (indices, rows) wire format: replicated
        storage, known index source, no compressor (the reference likewise kept
        sparse grads out of the compressor, all_reduce_synchronizer.py:132-173)."""
        return {n: p for n, p in self.params.items()
                if p.sparse and p.index_leaf and p.pspec == P()
                and p.compressor == COMP_NONE}

    # ------------------------------------------------- uneven (padded) storage
    def pad_params(self, tree: Any) -> Any:
        """Zero-pad unevenly-partitioned leaves to their physical storage shape.

        Works on params AND optimizer-state trees (optax states embed copies of the
        parameter tree, matched by name suffix). Traceable: usable inside jit.
        """
        return self._map_padded(tree, pad=True)

    def unpad_params(self, tree: Any) -> Any:
        """Slice padded-storage leaves back to their logical shapes (inverse of
        :meth:`pad_params`; differentiating through this slice yields zero
        gradients in the pad region, which is the masked update)."""
        return self._map_padded(tree, pad=False)

    def _map_padded(self, tree: Any, pad: bool) -> Any:
        if not self.has_padding:
            return tree
        import jax
        import jax.numpy as jnp

        padded = {n: p for n, p in self.params.items() if p.padded_dim is not None}
        match = _suffix_matcher(padded)

        def visit(path, leaf):
            name = match(_leaf_name(path))
            if name is not None:
                p = padded[name]
                ax, want = p.partition_axis, (p.logical_dim if pad else p.padded_dim)
                shape = getattr(leaf, "shape", ())
                if len(shape) > ax and shape[ax] == want:
                    if pad:
                        widths = [(0, 0)] * len(shape)
                        widths[ax] = (0, p.padded_dim - p.logical_dim)
                        return jnp.pad(leaf, widths)
                    return jax.lax.slice_in_dim(leaf, 0, p.logical_dim, axis=ax)
            return leaf

        return jax.tree_util.tree_map_with_path(visit, tree)

    def batch_pspec(self, ndim: int = 1) -> P:
        """Batch arrays shard their leading dim over all data-parallel axes
        (reference Remapper split batches along the first dim, remapper.py:109-118)."""
        return P(DP_AXES, *([None] * (ndim - 1)))

    def param_sharding_tree(self, mesh: Mesh, params: Any):
        """NamedSharding pytree for the parameter tree (by leaf path name)."""
        return _tree_shardings_by_name(mesh, params, {n: p.pspec for n, p in self.params.items()})

    # ------------------------------------------- ZeRO weight-update sharding
    def with_zero_update(self, mesh: Optional[Mesh] = None) -> "ShardingPlan":
        """A copy of this plan with ZeRO-style weight-update sharding ON.

        Every trainable parameter's ``opt_pspec`` shards the first axis that
        tiles evenly over ALL data-parallel axes (not just the PS family's
        ``reduce`` axis): optimizer-state memory drops to ``~size/dp`` per
        device, and a jitted step whose grads/updates are constrained to these
        specs lowers the update into reduce-scatter -> shard-local
        ``optimizer.update`` -> all-gather (the arXiv 2004.13336 formulation,
        inserted by XLA's SPMD partitioner under plain ``jit`` — no manual
        collectives). Parameters whose shape has no evenly-tiling free axis
        keep their existing (replicated / PS) opt sharding — the same
        degeneration tiny variables already had.

        ``mesh`` supplies the axis sizes the state will actually live on (the
        runner may legally rebuild a smaller mesh than the strategy was built
        for); defaults to the plan's own ``mesh_axes``."""
        if mesh is not None:
            axis_sizes = {a: mesh.shape.get(a, 1) for a in DP_AXES}
        else:
            axis_sizes = {a: self.mesh_axes.get(a, 1) for a in DP_AXES}
        dp = int(np.prod(list(axis_sizes.values()))) if axis_sizes else 1
        params = {}
        for name, p in self.params.items():
            pspec = _zero_update_pspec(p, dp)
            params[name] = dataclasses.replace(p, opt_pspec=pspec) \
                if pspec is not None else p
        plan = ShardingPlan(self.mesh_axes, params)
        plan.zero = True
        return plan

    def constrain_update(self, mesh: Mesh, tree: Any) -> Any:
        """``lax.with_sharding_constraint`` a params-shaped tree (gradients or
        optimizer updates) to the per-parameter ``opt_pspec``s — the
        reduce-scatter insertion point of the ZeRO update. Traceable."""
        return _constrain_tree(tree, _tree_shardings_by_name(
            mesh, tree, {n: p.opt_pspec for n, p in self.params.items()}))

    def constrain_opt(self, mesh: Mesh, opt_state: Any) -> Any:
        """Constrain an optimizer-state tree to the plan's opt shardings
        (shard-local moments stay sharded through the jitted step)."""
        return _constrain_tree(opt_state, self.opt_sharding_tree(mesh, opt_state))

    def constrain_params(self, mesh: Mesh, params: Any) -> Any:
        """Constrain an updated parameter tree back to its storage shardings —
        the all-gather closing the ZeRO update."""
        return _constrain_tree(params, self.param_sharding_tree(mesh, params))

    def opt_sharding_tree(self, mesh: Mesh, opt_state: Any):
        """NamedSharding pytree for the optimizer state.

        Optimizer states (optax) embed copies of the parameter tree (mu/nu/trace...):
        each leaf whose path ends with a parameter's path gets that parameter's
        ``opt_pspec``; everything else (step counters etc.) replicates. This is how
        the reference moved optimizer slots with their variable to the PS
        (``kernel/partitioner.py:570-573`` re-instantiated the optimizer over moved
        vars); here placement is a sharding, not a device string.
        """
        return _tree_shardings_by_name(
            mesh, opt_state, {n: p.opt_pspec for n, p in self.params.items()})

    def __repr__(self):
        kinds = collections.Counter(p.sync for p in self.params.values())
        return f"ShardingPlan(mesh={dict(self.mesh_axes)}, {dict(kinds)})"


def _spec_axes(pspec: P):
    """The mesh axes a PartitionSpec names, tuples flattened."""
    for entry in pspec or ():
        if entry is not None:
            yield from (entry if isinstance(entry, tuple) else (entry,))


def _first_tiling_axis_pspec(shape, base_pspec: P, axis_token,
                             divisor: int) -> Optional[P]:
    """The single "shard the first free evenly-tiling axis" rule shared by
    BOTH opt-state sharding derivations (PS-family ``reduce`` sharding and
    ZeRO's full-dp sharding), so the two can never drift.

    Puts ``axis_token`` on the first tensor axis that is not already taken by
    a model/expert axis in ``base_pspec`` and whose dim divides ``divisor``
    evenly; returns ``None`` when no axis tiles (callers pick their own
    degeneration)."""
    if divisor <= 1 or not shape:
        return None
    dims: list = list(base_pspec) if base_pspec \
        and len(base_pspec) == len(shape) else [None] * len(shape)
    for axis, dim in enumerate(shape):
        if dims[axis] is None and dim > 0 and dim % divisor == 0:
            dims[axis] = axis_token
            return P(*dims)
    return None


def _zero_update_pspec(p: ParamPlan, dp: int) -> Optional[P]:
    """The ZeRO opt-state PartitionSpec for one parameter, or ``None`` to keep
    the plan's existing one.

    The first free axis whose STORAGE dim (padded, for uneven partitioning)
    tiles evenly over the TOTAL data-parallel size gets the whole ``DP_AXES``
    tuple — every device is a data replica AND an update shard (meshes built
    by :func:`~autodist_tpu.parallel.mesh.build_mesh` always carry both axes,
    at size 1 when unused). Shapes with no evenly-tiling free axis return
    ``None`` (keep replicated/PS sharding — the degeneration tiny variables
    already had)."""
    shape = list(p.shape)
    if p.padded_dim is not None and p.partition_axis is not None:
        shape[p.partition_axis] = p.padded_dim  # opt state embeds padded storage
    return _first_tiling_axis_pspec(shape, p.pspec, DP_AXES, dp)


def _constrain_tree(tree: Any, shardings: Any) -> Any:
    import jax
    return jax.tree_util.tree_map(
        jax.lax.with_sharding_constraint, tree, shardings)


def _zero_style_opt_pspec(meta, param_pspec: P, reduce_size: int) -> P:
    """Optimizer-state sharding for a PS parameter.

    Shard the first axis that tiles evenly over the ``reduce`` axis and is not
    already taken by the model axis. Falls back to the parameter's own sharding when
    nothing tiles (small/odd shapes) — those replicate, which is also what the
    reference's single-PS placement degenerates to for tiny vars.
    """
    pspec = _first_tiling_axis_pspec(meta.shape, param_pspec,
                                     const.MESH_AXIS_REDUCE, reduce_size)
    return pspec if pspec is not None else param_pspec


def _leaf_name(path) -> str:
    from autodist_tpu.model_spec import _path_name
    return _path_name(path)


def _suffix_matcher(names):
    """Longest-suffix param-name matching (w vs emb/w): the single definition used
    by BOTH sharding derivation and pad/unpad, so the two can never disagree about
    which tree leaves are parameter-derived."""
    ordered = sorted(names, key=len, reverse=True)

    def match(leaf_name: str) -> Optional[str]:
        for name in ordered:
            if leaf_name == name or leaf_name.endswith("/" + name):
                return name
        return None

    return match


def _tree_shardings_by_name(mesh: Mesh, tree: Any, pspecs_by_name: Dict[str, P]):
    """Map each leaf to a NamedSharding by longest param-name suffix match."""
    import jax

    match = _suffix_matcher(pspecs_by_name)

    def choose(path, leaf):
        name = match(_leaf_name(path))
        if name is not None:
            pspec = pspecs_by_name[name]
            if _pspec_fits(pspec, getattr(leaf, "shape", ())):
                return NamedSharding(mesh, pspec)
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(choose, tree)


def _pspec_fits(pspec: P, shape) -> bool:
    if not pspec:
        return True
    return len(pspec) <= len(shape)
