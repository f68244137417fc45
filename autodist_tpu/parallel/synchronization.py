"""Gradient synchronization: the synchronizer kernels, TPU-native.

Reference counterparts:

- ``kernel/synchronization/all_reduce_synchronizer.py:102-130`` wrapped each gradient
  in ``collective_ops.all_reduce`` through a Compressor. Here the uncompressed path
  is simply the implicit psum XLA inserts for a sharded-batch ``value_and_grad``;
  the compressed path uses ``jax.shard_map`` so the cross-replica mean really rides
  the compressed (bfloat16 or low-rank) representation over ICI.
- ``kernel/synchronization/compressor.py``: ``NoneCompressor`` (:146-166),
  ``HorovodCompressor`` (:169-201, a dtype-cast codec) and ``HorovodCompressorEF``
  (:120-143, error feedback) map to NONE / BF16 / BF16_EF. ``PowerSGDCompressor``
  — which the reference drafted but left disabled (:208-284) — is implemented here
  as POWER_SGD: rank-r factorization M ~= P Q^T with one power iteration per step
  warm-started from the previous Q, QR orthogonalization, and error feedback; only
  the [m, r] and [n, r] factors cross the wire.
- Error-feedback residuals are **per data-parallel replica** (each worker keeps its
  own residual in the reference, ``compressor.py:120-143``): they are stored with a
  leading ``dp`` dimension sharded over the data axes, so in SPMD each device owns
  exactly its own residual slice.
- PS synchronizers need no explicit code here: weight-update sharding is expressed
  entirely through the plan's opt-state shardings (XLA emits the reduce-scatter /
  all-gather), replacing accumulators and token queues (``ps_synchronizer.py``).
- ZeRO weight-update sharding (``ShardingPlan.with_zero_update``, arXiv
  2004.13336) composes with everything here without code changes: the grad fn's
  outputs stay replicated-spec'd and the runner's step body reshards them at
  the constraint points, while the error-feedback residuals below were ALREADY
  ZeRO-form — a ``[dp, ...]`` leading dim sharded over the data axes, so each
  device owns exactly its 1/dp residual slice (``init_ef_state``/
  ``ef_partition_specs`` are the same treatment applied to compressor state).
"""

import dataclasses
import zlib
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from autodist_tpu.model_spec import ModelSpec
from autodist_tpu.parallel import plan as plan_lib
from autodist_tpu.parallel.plan import (COMP_BF16, COMP_BF16_EF, COMP_NONE,
                                        COMP_POWER_SGD, ShardingPlan)

PyTree = Any


@dataclasses.dataclass
class EFState:
    """Per-replica error-feedback residual for BF16_EF: ``error[i]`` is replica i's
    residual (leading dim = dp size, sharded over the data axes)."""

    error: jax.Array


@dataclasses.dataclass
class PowerSGDState:
    """PowerSGD carry: per-replica EF residual plus the shared Q factor.

    ``q`` is [n, r] and identical on every replica (it is rebuilt each step from the
    pmean'd factor), so it stays replicated; warm-starting it across steps is what
    makes one power iteration per step enough (reference draft compressor.py:208-284
    kept ``rank`` + a persistent Q the same way).
    """

    error: jax.Array   # [dp, *param_shape]
    q: jax.Array       # [n, r] where n = prod(param_shape[1:])


jax.tree_util.register_dataclass(EFState, data_fields=["error"], meta_fields=[])
jax.tree_util.register_dataclass(
    PowerSGDState, data_fields=["error", "q"], meta_fields=[])

# Compressor state crosses the PS transport (read/read_if_newer replies); the
# typed wire codec reconstructs these nodes through its registry, never by
# importing names off the socket (parallel/wire.py).
from autodist_tpu.parallel.wire import register_wire_dataclass  # noqa: E402

register_wire_dataclass(EFState)
register_wire_dataclass(PowerSGDState)


_WARNED: set = set()


def _warn_once(key: str, message: str) -> None:
    """Log a knob-downgrade warning once per process (grad fns rebuild per
    runner; the user needs the diagnostic, not a log flood)."""
    if key in _WARNED:
        return
    _WARNED.add(key)
    from autodist_tpu.utils import logging
    logging.warning(message)


def mesh_dp_size(mesh: Mesh) -> int:
    """Actual data-parallel size of a mesh: product of the DP axes it carries.

    The plan's ``dp_size`` reflects the device count the strategy was *built* for;
    the runner may legally rebuild a smaller mesh when running on fewer local chips
    (``DistributedRunner._mesh_from_plan``), so anything sized per-replica must use
    the mesh the state actually lives on."""
    return int(np.prod([mesh.shape[a] for a in plan_lib.DP_AXES if a in mesh.shape]))


def _powersgd_applies(shape) -> bool:
    # Like the reference draft, only matrix-shaped (rank >= 2) tensors are
    # factorized; vectors/scalars all-reduce exactly.
    return len(shape) >= 2


def _powersgd_rank(shape, rank: int) -> int:
    m, n = shape[0], int(np.prod(shape[1:]))
    return max(1, min(rank, m, n))


# --------------------------------------------------------------------- compressors

def compress(x: jax.Array, kind: int) -> jax.Array:
    if kind in (COMP_BF16, COMP_BF16_EF):
        return x.astype(jnp.bfloat16)
    return x


def decompress(x: jax.Array, dtype) -> jax.Array:
    return x.astype(dtype)


class _SyncResult:
    """One parameter's synchronized gradient + its new compressor state. A plain
    (non-pytree) object so a tree of these keeps the parameter-tree structure."""

    __slots__ = ("synced", "state")

    def __init__(self, synced, state):
        self.synced = synced
        self.state = state


def _powersgd_sync(g: jax.Array, ef: PowerSGDState, pmean=None) -> _SyncResult:
    """One PowerSGD round inside shard_map: M = g + e; P = pmean(M Q); P_hat = QR(P);
    Q' = pmean(M^T P_hat); synced = P_hat Q'^T; e' = M - synced (local).
    ``pmean`` injects the spec-aware (possibly hierarchical) reduce — the factors
    ARE the dominant transfers, so the ICI/DCN knob must apply to them."""
    if pmean is None:
        pmean = lambda x: jax.lax.pmean(x, plan_lib.DP_AXES)  # noqa: E731
    shape = g.shape
    m, n = shape[0], int(np.prod(shape[1:]))
    err = ef.error[0]                               # this replica's residual slice
    mat = (g + err).reshape(m, n).astype(jnp.float32)
    p_fac = pmean(mat @ ef.q)                       # [m, r] on the wire
    p_hat, _ = jnp.linalg.qr(p_fac)                 # orthonormal [m, r]
    q_new = pmean(mat.T @ p_hat)                    # [n, r] on the wire
    approx = p_hat @ q_new.T                        # identical everywhere
    new_err = (mat - approx).reshape(shape).astype(g.dtype)
    synced = approx.reshape(shape).astype(g.dtype)
    return _SyncResult(synced, PowerSGDState(error=new_err[None],
                                             q=q_new.astype(ef.q.dtype)))


# ------------------------------------------------------------------ grad functions

def _lowering(sharding_plan: ShardingPlan, mesh: Mesh):
    """What decides :func:`make_grad_fn`'s lowering on this mesh: ``(dp,
    sparse_wire, hierarchical_ok, requested_dcn, honor_dcn, use_explicit)``."""
    dp = mesh_dp_size(mesh)
    sparse_wire = sharding_plan.sparse_wire_params if dp > 1 else {}
    spec_dcn = plan_lib.strategy_pb2.AllReduceSynchronizer.DCN
    # Two-phase reduce needs both DP axes populated (inner = intra-slice tier).
    hierarchical_ok = all(mesh.shape.get(a, 1) > 1 for a in plan_lib.DP_AXES)
    requested_dcn = any(p.spec == spec_dcn
                        for p in sharding_plan.params.values())
    # A DCN (hierarchical-reduce) request is itself a reason to take the
    # explicit lowering: on the implicit path XLA owns the reduction schedule
    # and the knob would silently do nothing.
    honor_dcn = (requested_dcn and dp > 1 and hierarchical_ok
                 and sharding_plan.all_params_replicated)
    use_explicit = (sharding_plan.has_compression or bool(sparse_wire)
                    or honor_dcn)
    return (dp, sparse_wire, hierarchical_ok, requested_dcn, honor_dcn,
            use_explicit)


def batch_trace_shards(sharding_plan: ShardingPlan, mesh: Mesh) -> int:
    """How many data shards one trace of :func:`make_grad_fn`'s loss stands
    for: the data-parallel size under the implicit lowering, whose trace sees
    the global batch, and 1 under the explicit one, traced a shard at a time.
    What a trace counts of its own arrays (the bytes a ``KEPT`` list keeps)
    over this is a chip's share — an upper bound of it where the compiler
    also splits such an array over a model axis."""
    dp, *_, use_explicit = _lowering(sharding_plan, mesh)
    explicit = use_explicit and sharding_plan.all_params_replicated
    return 1 if explicit else dp


def make_grad_fn(sharding_plan: ShardingPlan, model_spec: ModelSpec, mesh: Mesh,
                 loss_fn: Callable, has_aux: bool = False) -> Callable:
    """Build ``grad_fn(params, batch, ef_state) -> (grads, loss, aux, new_ef_state)``.

    Two lowerings:

    - **Implicit** (no compressor anywhere): plain ``value_and_grad`` of the global
      loss; the batch is sharded over the data axes, so XLA inserts the gradient
      all-reduce (and, with sharded opt state, the reduce-scatter) itself.
    - **Explicit** (a compressor somewhere, or a sparse param with a known index
      source): ``jax.shard_map`` over the data axes — each shard computes a local
      gradient, then per parameter either compresses + ``lax.pmean``s (bfloat16 /
      PowerSGD factors on the wire), or for sparse params all-gathers
      (indices, touched rows) and rebuilds the dense gradient by segment-sum — the
      reference's sparse all-gather wire path
      (``all_reduce_synchronizer.py:132-173``): for a large embedding the wire
      carries ~batch rows instead of the whole matrix. Error feedback keeps a
      per-replica residual: x = g + ef; send compress(x);
      ef' = x - decompress(compress(x)).
    """
    dp, sparse_wire, hierarchical_ok, requested_dcn, honor_dcn, use_explicit \
        = _lowering(sharding_plan, mesh)
    spec_dcn = plan_lib.strategy_pb2.AllReduceSynchronizer.DCN
    if requested_dcn and dp > 1 and not honor_dcn:
        msg = ("spec=DCN (hierarchical two-phase reduce) was requested but "
               "cannot be honored on this mesh/strategy (%s); gradients use a "
               "single-phase reduce" % (
                   "mesh lacks a populated inner DP axis" if not hierarchical_ok
                   else "partitioned parameters use the implicit SPMD lowering"))
        _warn_once(msg, msg)  # keyed by the full message: distinct downgrade
        # reasons in one process each get their own diagnostic

    def implicit(params, batch, ef_state):
        if has_aux:
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            aux = ()
        return grads, loss, aux, ef_state

    if not use_explicit:
        return _with_stored_shards(sharding_plan, implicit)

    if not sharding_plan.all_params_replicated:
        if sharding_plan.has_compression:
            raise NotImplementedError(
                "Gradient compression currently requires replicated parameters "
                "(AllReduce-family strategies); partitioned parameters with a "
                "compressor are not supported in one strategy")
        # Sparse wire rides the shard_map path, which needs every parameter
        # replicated; partitioned models keep the implicit SPMD lowering.
        from autodist_tpu.utils import logging
        logging.info("Sparse all-gather wire disabled: model has partitioned "
                     "parameters; using implicit dense synchronization")
        return _with_stored_shards(sharding_plan, implicit)

    from autodist_tpu.model_spec import _path_name as name_of
    plans_by_name = dict(sharding_plan.params)

    def _pmean(x, spec: int):
        """Cross-replica mean, honoring the network-tier knob: DCN requests a
        hierarchical two-phase reduce — inner DP axis first (lay it out on ICI
        within a slice), then the outer axis (DCN across slices) — the TPU-native
        reading of the reference's NCCL/RING communication hint
        (all_reduce_synchronizer.py:102-130). AUTO/ICI is one joint reduce."""
        if spec == spec_dcn and hierarchical_ok:
            x = jax.lax.pmean(x, plan_lib.DP_AXES[1])  # intra-slice (ICI)
            return jax.lax.pmean(x, plan_lib.DP_AXES[0])  # cross-slice (DCN)
        return jax.lax.pmean(x, plan_lib.DP_AXES)

    def local_fn(params, batch, ef_state):
        if has_aux:
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            aux = ()
        # The reduction only, under its own scope: it nests in the runner's
        # ``step.grad``, so a device trace can tell the sync from the backward.
        with jax.named_scope("step.grad_sync"):
            return sync_fn(grads, loss, aux, ef_state, batch)

    def sync_fn(grads, loss, aux, ef_state, batch):
        # ---- collect leaves in traversal order so buckets can span the tree ----
        collected = []

        def _collect(path, g, ef):
            collected.append((path, g, ef))
            return 0

        jax.tree_util.tree_map_with_path(_collect, grads, ef_state)

        # ---- gradient bucketing: params sharing a fusion group id reduce as one
        # concatenated buffer (the reference fused CollectiveReduce via
        # ScopedAllocator with these same group ids, runner.py:41-46). Stateless
        # and EF codecs bucket; PowerSGD (matrix-structured) and the sparse wire
        # stay per-leaf. ----
        buckets = {}
        for path, g, ef in collected:
            pp = plans_by_name.get(name_of(path))
            kind = pp.compressor if pp else COMP_NONE
            if pp is None or pp.name in sparse_wire or kind == COMP_POWER_SGD:
                continue
            if kind == COMP_BF16_EF and not isinstance(ef, EFState):
                continue  # per-leaf path raises the diagnostic TypeError
            if not getattr(g, "ndim", None):
                continue
            buckets.setdefault((pp.group, kind, pp.spec, g.dtype),
                               []).append((path, g, ef))

        bucketed_results = {}  # keyed by leaf path name
        for (group, kind, spec, dtype), members in buckets.items():
            if len(members) < 2:
                continue
            xs = [g + ef.error[0] if kind == COMP_BF16_EF else g
                  for _, g, ef in members]
            flat = jnp.concatenate([x.reshape(-1) for x in xs])
            synced_flat = decompress(_pmean(compress(flat, kind), spec), dtype)
            offset = 0
            for (path, g, ef), x in zip(members, xs):
                part = synced_flat[offset:offset + x.size].reshape(g.shape)
                offset += x.size
                if kind == COMP_BF16_EF:
                    new_err = x - decompress(compress(x, kind), g.dtype)
                    bucketed_results[name_of(path)] = _SyncResult(
                        part, EFState(error=new_err[None]))
                else:
                    bucketed_results[name_of(path)] = _SyncResult(part, ef)

        def sync_leaf(path, g, ef):
            param_plan = plans_by_name.get(name_of(path))
            kind = param_plan.compressor if param_plan else COMP_NONE
            spec = param_plan.spec if param_plan else 0
            if param_plan is not None and param_plan.name in sparse_wire:
                idx = _batch_leaf_by_name(batch, param_plan.index_leaf)
                if idx is not None:
                    return _SyncResult(_sparse_allgather_sync(g, idx, dp), ef)
            if kind == COMP_POWER_SGD and isinstance(ef, PowerSGDState):
                return _powersgd_sync(g, ef, pmean=lambda x: _pmean(x, spec))
            if kind == COMP_POWER_SGD and _powersgd_applies(g.shape):
                # A matrix-shaped POWER_SGD param must carry a PowerSGDState; falling
                # through would silently all-reduce the full gradient uncompressed.
                raise TypeError(
                    f"POWER_SGD parameter {name_of(path)!r} has no PowerSGDState "
                    f"(got {type(ef).__name__}); init_ef_state was bypassed")
            if kind == COMP_BF16_EF and isinstance(ef, EFState):
                x = g + ef.error[0]
                synced = decompress(_pmean(compress(x, kind), spec), g.dtype)
                new_err = x - decompress(compress(x, kind), g.dtype)
                return _SyncResult(synced, EFState(error=new_err[None]))
            if kind == COMP_BF16_EF:
                raise TypeError(
                    f"BF16_EF parameter {name_of(path)!r} has no EFState "
                    f"(got {type(ef).__name__}); init_ef_state was bypassed")
            if kind == COMP_BF16:
                # Plain cast codec, reference HorovodCompressor semantics.
                synced = decompress(_pmean(compress(g, COMP_BF16), spec), g.dtype)
                return _SyncResult(synced, ef)
            # NONE, or POWER_SGD on a vector/scalar: exact all-reduce.
            return _SyncResult(_pmean(g, spec), ef)

        def finalize(path, g, ef):
            return bucketed_results.get(name_of(path)) or sync_leaf(path, g, ef)

        results = jax.tree_util.tree_map_with_path(finalize, grads, ef_state)
        synced = jax.tree_util.tree_map(lambda r: r.synced, results)
        new_ef = jax.tree_util.tree_map(lambda r: r.state, results)
        loss = jax.lax.pmean(loss, plan_lib.DP_AXES)
        aux = jax.tree_util.tree_map(lambda a: jax.lax.pmean(a, plan_lib.DP_AXES), aux)
        return synced, loss, aux, new_ef

    batch_spec_fn = _batch_spec_maker(sharding_plan, dp=mesh_dp_size(mesh))

    def explicit(params, batch, ef_state):
        batch_specs = jax.tree_util.tree_map(batch_spec_fn, batch)
        replicated = jax.tree_util.tree_map(lambda _: P(), params)
        ef_specs = ef_partition_specs(ef_state)
        out = jax.shard_map(
            local_fn, mesh=mesh,
            in_specs=(replicated, batch_specs, ef_specs),
            out_specs=(replicated, P(), P(), ef_specs),
            check_vma=False,
        )(params, batch, ef_state)
        return out

    return explicit


def _with_stored_shards(sharding_plan: ShardingPlan, grad_fn: Callable):
    """``grad_fn`` itself, or, where the plan stores parameters as shares over
    a data axis (``strategy.FullySharded``), ``grad_fn`` traced inside
    ``parallel.mesh.stored_shards``: a kernel under ``per_device`` then takes
    such a leaf as its share and gathers it in its body. (Called at the
    implicit lowering's two returns and defined down here: the lines above
    are frames of every kernel's call.)"""
    stored = sharding_plan.data_shard_axes()
    if not stored:
        return grad_fn
    from autodist_tpu.parallel.mesh import stored_shards

    def in_stored_shards(*args):
        with stored_shards(stored):
            return grad_fn(*args)
    return in_stored_shards


def _batch_leaf_by_name(batch: PyTree, leaf_name: str):
    from autodist_tpu.model_spec import _path_name
    for path, leaf in jax.tree_util.tree_flatten_with_path(batch)[0]:
        if _path_name(path) == leaf_name:
            return leaf
    return None


def _sparse_allgather_sync(g: jax.Array, idx: jax.Array, dp: int) -> jax.Array:
    """Sparse gradient sync: ship (indices, touched rows), not the dense matrix.

    ``g`` is this replica's dense scatter-add gradient of an embedding used only
    via gather, so it is nonzero only on rows its local indices touch. Each
    duplicate index contributes 1/k of its row so the local scatter-sum of the
    shipped contributions reconstructs ``g`` exactly; the all-gather then carries
    [global_batch, dim] + [global_batch] over the wire instead of [vocab, dim]
    (reference all_reduce_synchronizer.py:132-173 gathered IndexedSlices the same
    way). Result equals ``pmean(g)`` bit-for-bit up to float summation order.
    """
    vocab = g.shape[0]
    flat_idx = idx.reshape(-1).astype(jnp.int32)
    # Reproduce jnp.take's negative wrap (the detected provenance allows exactly
    # the {idx, idx+vocab} select pattern); out-of-range indices contributed no
    # gradient (FILL_OR_DROP), so mask them out of the reconstruction too.
    flat_idx = jnp.where(flat_idx < 0, flat_idx + vocab, flat_idx)
    valid = (flat_idx >= 0) & (flat_idx < vocab)
    safe_idx = jnp.where(valid, flat_idx, 0)
    rows = jnp.take(g, safe_idx, axis=0)
    counts = jax.ops.segment_sum(valid.astype(jnp.float32), safe_idx,
                                 num_segments=vocab)
    inv = jnp.where(valid, 1.0 / jnp.maximum(counts[safe_idx], 1.0), 0.0)
    contrib = rows * inv.astype(g.dtype).reshape((-1,) + (1,) * (rows.ndim - 1))
    all_idx = jax.lax.all_gather(safe_idx, plan_lib.DP_AXES, tiled=True)
    all_contrib = jax.lax.all_gather(contrib, plan_lib.DP_AXES, tiled=True)
    summed = jax.ops.segment_sum(all_contrib, all_idx, num_segments=vocab)
    return (summed / dp).astype(g.dtype)


def _batch_spec_maker(sharding_plan: ShardingPlan, dp: int):

    def spec_for(leaf):
        shape = getattr(leaf, "shape", ())
        if shape and shape[0] % dp == 0:
            return sharding_plan.batch_pspec(len(shape))
        return P()

    return spec_for


# ------------------------------------------------------------- compressor state

def init_ef_state(sharding_plan: ShardingPlan, params: PyTree,
                  mesh: Optional[Mesh] = None) -> PyTree:
    """Compressor state tree, shaped like ``params`` at the top level: an
    :class:`EFState` for BF16_EF parameters, a :class:`PowerSGDState` for matrix
    POWER_SGD parameters, and 0-d zeros elsewhere (so the tree rides the same
    sharding derivation). Residuals carry a leading ``dp`` dimension — one slice per
    data-parallel replica (the reference kept the residual as per-worker Python
    state inside the compressor object, ``compressor.py:120-143``). This IS the
    ZeRO sharding treatment for compressor state: residual memory is already
    ``size/dp`` per device whether or not the plan enables
    ``with_zero_update`` for the optimizer state (PowerSGD's ``q`` must stay
    replicated — every replica contracts against the full factor each step).

    With ``mesh``, the residuals are allocated directly with their sharding (a
    ``[dp, ...]`` residual materialized replicated first would cost dp× parameter
    memory on one device — exactly the scale compression targets)."""
    from autodist_tpu.model_spec import _path_name
    dp = mesh_dp_size(mesh) if mesh is not None else sharding_plan.dp_size
    plans = sharding_plan.params

    def leaf(path, x):
        param_plan = plans.get(_path_name(path))
        kind = param_plan.compressor if param_plan else COMP_NONE
        if kind == COMP_BF16_EF:
            return EFState(error=jnp.zeros((dp,) + x.shape, dtype=x.dtype))
        if kind == COMP_POWER_SGD and _powersgd_applies(x.shape):
            r = _powersgd_rank(x.shape, param_plan.power_sgd_rank)
            n = int(np.prod(x.shape[1:]))
            # Deterministic orthonormal warm start, seeded by the parameter name so
            # every process initializes identically without coordination.
            key = jax.random.PRNGKey(zlib.crc32(param_plan.name.encode()))
            q0, _ = jnp.linalg.qr(jax.random.normal(key, (n, r), jnp.float32))
            return PowerSGDState(error=jnp.zeros((dp,) + x.shape, dtype=x.dtype), q=q0)
        return jnp.zeros((), dtype=x.dtype)

    # Only shapes/dtypes matter: build from metadata so no parameter is ever
    # transferred (a params operand would commit a fully-replicated copy of the
    # model to every device before the plan's shardings are applied).
    meta = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)), params)

    def build():
        return jax.tree_util.tree_map_with_path(leaf, meta)

    if mesh is None:
        return build()
    abstract = jax.eval_shape(build)
    shardings = ef_sharding_tree(mesh, abstract)
    with mesh:
        return jax.jit(build, out_shardings=shardings)()


def ef_partition_specs(ef_state: PyTree) -> PyTree:
    """PartitionSpecs for a compressor-state tree: ``error`` leaves shard their
    leading (replica) dim over the data axes; everything else replicates."""

    def spec(path, x):
        last = path[-1] if path else None
        if (isinstance(last, jax.tree_util.GetAttrKey) and last.name == "error"
                and getattr(x, "ndim", 0) >= 1):
            return P(plan_lib.DP_AXES, *([None] * (x.ndim - 1)))
        return P()

    return jax.tree_util.tree_map_with_path(spec, ef_state)


def ef_sharding_tree(mesh: Mesh, ef_state: PyTree) -> PyTree:
    """NamedSharding pytree for the compressor state (used for jit in/out shardings)."""
    from jax.sharding import NamedSharding
    specs = ef_partition_specs(ef_state)
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs,
                                  is_leaf=lambda x: isinstance(x, P))


# --------------------------------------------------------- wire-push compression

@dataclasses.dataclass
class SparseRows:
    """Row-sparse gradient wire frame: only the TOUCHED rows of an
    embedding-style gradient cross the PS wire.

    ``indices`` [k] are the unique touched row ids, ``rows`` [k, ...] the
    gradient rows at those ids, ``shape`` the dense shape the server
    scatter-applies into. Registered with the wire codec (rides as an ``o``
    frame whose array fields borrow like any other) but deliberately NOT
    registered as a jax pytree node: the server's densify pass must see it
    as a tree LEAF, not recurse into its fields."""

    indices: Any
    rows: Any
    shape: Any


register_wire_dataclass(SparseRows)


def densify_sparse_rows(tree: PyTree) -> PyTree:
    """Server-side scatter-apply: expand every :class:`SparseRows` leaf back
    to its dense gradient (zeros off the touched rows — exact, because a
    gather-only embedding's dense gradient IS zero off the touched rows;
    that provenance is what lets the plan mark the param sparse at all).
    Scatter-ADD, so duplicate indices — which a well-formed push never
    ships — still sum rather than silently last-write-wins."""

    def leaf(x):
        if not isinstance(x, SparseRows):
            return x
        rows = np.asarray(x.rows)
        dense = np.zeros(tuple(int(d) for d in x.shape), rows.dtype)
        if rows.size:
            np.add.at(dense, np.asarray(x.indices).reshape(-1), rows)
        return dense

    return jax.tree_util.tree_map(
        leaf, tree, is_leaf=lambda x: isinstance(x, SparseRows))


class WirePushCompressor:
    """Host-side gradient compressor for the remote PS push path.

    Sits between ``grads = _to_host(grads)`` and ``call("apply", ...)`` in
    :class:`~autodist_tpu.parallel.ps_transport.RemotePSWorker` — purely a
    transport concern: the server dequantizes/densifies on decode, so its
    apply path (and the plan's in-graph compressors) never change.

    Three regimes per leaf, mirroring the reference draft's rank gate:

    - **sparse push** (exact): params the plan marked row-sparse ship as
      :class:`SparseRows` — only the rows the batch's index leaf touched.
      No quantization, no residual; byte-for-byte the dense apply's result.
    - **quantized push** (lossy + error feedback): float leaves with
      ``ndim >= 2`` and at least ``min_bytes`` ship as ``wire.quantize``
      frames. The quantization residual ``x - dequantize(quantize(x))`` is
      kept per leaf in the existing :class:`EFState` machinery and added
      back before the NEXT quantize, so the compressed run tracks the exact
      run (int8 without EF is the documented divergent negative control).
    - **bypass** (exact): vectors, scalars, ints, and anything under the
      size floor ship untouched — the size floor is where compression's
      scale bytes and host cost stop paying for themselves.

    Cumulative ``bytes_in`` / ``bytes_out`` / ``bytes_saved`` /
    ``quantize_s`` stats mirror into the ``ps.wire.*`` registry counters
    when telemetry is on (the adtop/adfleet compression line and the
    profile block the cost model's ``quantize_bytes_per_s`` fit reads)."""

    def __init__(self, wire_dtype: str = "", *, min_bytes: Optional[int] = None,
                 error_feedback: bool = True,
                 sparse_params: Optional[dict] = None):
        from autodist_tpu import const
        from autodist_tpu.parallel import wire as wire_lib
        wire_dtype = str(wire_dtype or "").lower()
        if wire_dtype in ("off", "none", "0"):
            wire_dtype = ""
        if wire_dtype and wire_dtype not in wire_lib.WIRE_DTYPES:
            raise ValueError(f"unknown wire dtype {wire_dtype!r}; valid: "
                             f"off, {', '.join(wire_lib.WIRE_DTYPES)}")
        self.wire_dtype = wire_dtype
        self.min_bytes = int(const.ENV.AUTODIST_COMPRESS_MIN_BYTES.val
                             if min_bytes is None else min_bytes)
        self.error_feedback = bool(error_feedback)
        # param name -> batch index-leaf name (plan.sparse_wire_params)
        self.sparse_params = dict(sparse_params or {})
        self._residuals: dict = {}   # param name -> EFState
        self.bytes_in = 0            # dense bytes of every compressed leaf
        self.bytes_out = 0           # wire bytes those leaves actually ship
        self.bytes_saved = 0
        self.quantize_s = 0.0

    @property
    def active(self) -> bool:
        return bool(self.wire_dtype) or bool(self.sparse_params)

    def _sparse_leaf(self, name: str, g: np.ndarray, batch):
        from autodist_tpu.parallel import wire as wire_lib  # noqa: F401
        idx = _batch_leaf_by_name(batch, self.sparse_params[name]) \
            if batch is not None else None
        if idx is None:
            return None
        vocab = g.shape[0]
        flat = np.asarray(idx).reshape(-1).astype(np.int64)
        flat = np.where(flat < 0, flat + vocab, flat)   # jnp.take's wrap
        uniq = np.unique(flat[(flat >= 0) & (flat < vocab)])
        return SparseRows(indices=uniq, rows=np.ascontiguousarray(g[uniq]),
                          shape=tuple(int(d) for d in g.shape))

    def compress(self, grads: PyTree, batch: PyTree = None):
        """Returns ``(wire_tree, has_sparse)`` — the tree to push (leaves
        replaced by :class:`SparseRows` / ``wire.QuantizedArray` where the
        regime applies) and whether any leaf went sparse (the worker then
        uses the ``apply_sparse`` opcode)."""
        import time as _time
        from autodist_tpu import telemetry
        from autodist_tpu.model_spec import _path_name
        from autodist_tpu.parallel import wire as wire_lib
        t0 = _time.perf_counter()
        saved = quantized = 0
        has_sparse = False

        def leaf(path, g):
            nonlocal saved, quantized, has_sparse
            g = np.asarray(g)
            name = _path_name(path)
            if name in self.sparse_params and g.ndim >= 2:
                sp = self._sparse_leaf(name, g, batch)
                if sp is not None:
                    has_sparse = True
                    out_b = sp.rows.nbytes + sp.indices.nbytes
                    self.bytes_in += g.nbytes
                    self.bytes_out += out_b
                    saved += max(0, g.nbytes - out_b)
                    return sp
            if (self.wire_dtype and np.issubdtype(g.dtype, np.floating)
                    and g.ndim >= 2 and g.nbytes >= self.min_bytes):
                x = g
                prev = self._residuals.get(name)
                if prev is not None:
                    x = g + np.asarray(prev.error[0])
                qa = wire_lib.quantize(x, self.wire_dtype)
                if self.error_feedback:
                    # Residual rides the existing EFState carrier (leading
                    # [dp] dim) — one state shape across every compressor.
                    self._residuals[name] = EFState(
                        error=(x - wire_lib.dequantize(qa))[None])
                self.bytes_in += g.nbytes
                self.bytes_out += qa.wire_nbytes
                saved += max(0, g.nbytes - qa.wire_nbytes)
                quantized += g.nbytes
                return qa
            return g

        out = jax.tree_util.tree_map_with_path(leaf, grads)
        dt = _time.perf_counter() - t0
        self.bytes_saved += saved
        self.quantize_s += dt
        if telemetry.enabled():
            telemetry.counter("ps.wire.bytes_saved").inc(saved)
            telemetry.counter("ps.wire.bytes_quantized").inc(quantized)
            telemetry.counter("wire.quantize_s").inc(dt)
        return out, has_sparse
