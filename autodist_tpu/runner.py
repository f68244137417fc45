"""Execution runtime: DistributedRunner (reference WrappedSession + Remapper).

The reference's steady-state step (``runner.py:117-132``) ran a grpc session with a
feed/fetch remapper splitting the host batch across replicas (``remapper.py:81-123``)
and contracting fetches (``:125-185``). Here the step is one jitted SPMD program over
the mesh:

- feeds: host arrays whose leading dim is divisible by the data-parallel size are
  device_put with the batch sharding (the split); everything else replicates (the
  duplicate) — same polymorphism as the reference's Remapper.
- fetches: the loss (and aux metrics) come back as replicated scalars — the
  "master replica value" contraction is a no-op in SPMD.
- initializers-at-construction (reference ``runner.py:97-100``) becomes
  ``init(params)``: placing params/opt-state/EF-state onto the mesh per the plan.
"""

import dataclasses
import time
import weakref
import zlib
from typing import Any, Callable, Optional, Tuple

import jax
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from autodist_tpu import telemetry
from autodist_tpu.telemetry import profiling as _profiling
from autodist_tpu.model_spec import ModelSpec
from autodist_tpu.parallel import synchronization
from autodist_tpu.parallel.mesh import build_mesh
from autodist_tpu.parallel.plan import ShardingPlan
from autodist_tpu.utils import compile_cache, logging

PyTree = Any


def place_host_value(leaf, sharding) -> jax.Array:
    """Place a host value with ``sharding``, tolerating heterogeneous processes.

    ``jax.device_put`` onto a non-fully-addressable sharding runs a cross-process
    value check built on ``process_allgather``, which requires every process to
    have the same local device count — exactly what a heterogeneous cluster
    (reference ``resource_specs/r4.yml``, 2+1 GPUs) violates. Building the array
    from per-shard callbacks sidesteps the check; every process holds the same
    full host value by construction (same batch protocol as the reference's
    per-worker re-execution)."""
    if sharding.is_fully_addressable:
        return jax.device_put(leaf, sharding)
    arr = np.asarray(leaf)
    return jax.make_array_from_callback(arr.shape, sharding, lambda idx: arr[idx])


@dataclasses.dataclass(frozen=True)
class FeedLayout:
    """The static description of a runner's feed remapping — what the
    input-data plane (:mod:`autodist_tpu.data.prefetch`) keys per-host
    sharding and async transfers off, so a prefetch pipeline can never
    place a batch differently than :meth:`DistributedRunner.shard_batch`
    would. ``dp`` is the data-parallel extent, ``accum`` the micro-batch
    split, ``batch_pspec(ndim)`` the plan's batch partition spec."""

    mesh: Any
    plan: Any
    dp: int
    accum: int

    def batch_pspec(self, ndim: int):
        return self.plan.batch_pspec(ndim)


@jax.tree_util.register_pytree_node_class
class MicroBatched:
    """Marker wrapping a batch leaf laid out ``[accum_steps, micro_batch, ...]``.

    Produced by ``shard_batch`` when gradient accumulation is on; the step scans
    axis 0. Being a pytree *node* (not a bare array) makes "which leaves are
    micro-batched" part of the jit cache key, so a batch structure change can
    never silently reuse a stale compiled step.
    """

    def __init__(self, value):
        self.value = value

    def tree_flatten(self):
        return (self.value,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0])


def _is_micro(leaf) -> bool:
    return isinstance(leaf, MicroBatched)


class BatchBlock:
    """K pre-sharded batches stacked along a leading step axis.

    Built by :meth:`DistributedRunner.shard_block` (or
    ``data.loader.device_prefetch(..., unroll=K)``) and consumed by
    :meth:`DistributedRunner.run_many`, which scans the step body over the
    leading axis — one compiled dispatch for K optimizer steps. A host-side
    handle, not a pytree: ``tree`` is the on-device stacked batch pytree and
    ``length`` the number of steps it carries."""

    __slots__ = ("tree", "length")

    def __init__(self, tree, length: int):
        self.tree = tree
        self.length = length

    def __len__(self) -> int:
        return self.length


@dataclasses.dataclass
class TrainState:
    """One training step's carried state (a pytree)."""

    step: jax.Array
    params: PyTree
    opt_state: PyTree
    ef_state: PyTree   # error-feedback residuals (zeros-scalars when unused)
    # Static (untraced) reference to the ShardingPlan that shaped this state, so the
    # Saver can slice padded uneven-partition storage back to logical shapes without
    # the caller having to remember which runner the state came from. Compared by
    # identity for jit caching — one runner always reuses one plan object.
    plan: Any = None


jax.tree_util.register_dataclass(
    TrainState, data_fields=["step", "params", "opt_state", "ef_state"],
    meta_fields=["plan"])


class _CompileProbe:
    """Times one first-of-its-signature dispatch and books it as compilation.

    jit compiles synchronously inside the first call for a new input
    signature (tracing + lowering + XLA compile happen before the program is
    enqueued), so that call's wall time IS the compile cost to within one
    async dispatch. Wraps the would-be dispatch span with a ``jit.compile``
    span and, on exit, bumps ``jit.cache_miss`` and accumulates
    ``jit.compile_s`` in the telemetry registry — and, when the profiling
    plane armed a ``cost_cb``, hands it the compile seconds so the program's
    static cost record (XLA cost analysis) lands in the per-signature cache.
    Constructed only in enabled mode
    (:meth:`DistributedRunner._dispatch_span`)."""

    __slots__ = ("_inner", "_t0", "_cost_cb")

    def __init__(self, inner, cost_cb=None):
        self._inner = inner
        self._t0 = 0.0
        self._cost_cb = cost_cb

    def __enter__(self):
        self._inner.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        telemetry.counter("jit.cache_miss").inc()
        telemetry.counter("jit.compile_s").inc(dt)
        if self._cost_cb is not None and exc[0] is None:
            self._cost_cb(dt)
        return self._inner.__exit__(*exc)


class _StepAnnotated:
    """A dispatch span inside ``jax.profiler.StepTraceAnnotation("train",
    step_num=...)``, which marks the step in a profiler session's own trace.
    Constructed only in enabled mode
    (:meth:`DistributedRunner._dispatch_span`)."""

    __slots__ = ("_inner", "_step")

    def __init__(self, inner, step_num: int):
        self._inner = inner
        self._step = jax.profiler.StepTraceAnnotation("train",
                                                      step_num=step_num)

    def __enter__(self):
        self._step.__enter__()
        self._inner.__enter__()
        return self

    def __exit__(self, *exc):
        try:
            return self._inner.__exit__(*exc)
        finally:
            self._step.__exit__(*exc)


class DistributedRunner:
    """Compiles and runs the distributed train step for one (strategy, model).

    Counterpart of reference ``WrappedSession`` (``runner.py:78-132``): constructed
    from the *compiled* strategy, owns the mesh, shards state, steps batches.
    """

    # Whether run_many's fused multi-step scan is available. The async/remote
    # regimes override to False: their parameter service applies gradients
    # host-step by host-step, so there is no on-device K-step program to fuse.
    supports_run_many = True
    # Optimizer steps dispatched with telemetry on: the ``step_num`` of the
    # dispatch's StepTraceAnnotation (``state.step`` lives on the device, and
    # reading it would be a sync).
    _annotated_steps = 0

    def __init__(self, compiled_strategy, model_spec: ModelSpec, loss_fn: Callable,
                 optimizer, mesh: Optional[Mesh] = None, has_aux: bool = False,
                 donate_state: bool = True, plan: Optional[ShardingPlan] = None,
                 accumulation_steps: int = 1, batch_size: Optional[int] = None,
                 zero: Optional[Any] = None, health: Optional[bool] = None):
        if accumulation_steps < 1:
            raise ValueError("accumulation_steps must be >= 1")
        # Training-health monitors (``health=None`` reads AUTODIST_HEALTH):
        # when on, the step body additionally computes the fused numerics
        # bundle (telemetry/health.py) — four f32 scalars in the SAME
        # compiled program, read back only at the train loop's log
        # boundaries. Off (the default) leaves the program byte-identical.
        if health is None:
            from autodist_tpu import const
            health = const.ENV.AUTODIST_HEALTH.val
        self.health = bool(health)
        # The most recent step's device-side health bundle (float32[4] per
        # telemetry.health.BUNDLE_FIELDS; an unroll block arrives reduced).
        # A device array — callers device_get it at their own sync points.
        self.last_health = None
        # ZeRO-style weight-update sharding (arXiv 2004.13336; ``zero=None``
        # reads AUTODIST_ZERO): 0/False off, 1/True on, N>1 on with N
        # server-side PS apply shards (the async regime's knob). On the
        # synchronous path "on" reshards the plan's opt-state specs over the
        # data-parallel axes and constrains grads/updates/params in the step
        # body, so XLA lowers the update into reduce-scatter -> shard-local
        # optimizer.update -> all-gather.
        if zero is None:
            from autodist_tpu import const
            zero = const.ENV.AUTODIST_ZERO.val
        self.zero = int(zero)
        # Explicit global batch size for micro-batch splitting; when None it is
        # inferred per batch as the modal leading dim (see shard_batch).
        self._batch_size = batch_size
        self._model_spec = model_spec
        self._loss_fn = loss_fn
        self._optimizer = optimizer
        self._has_aux = has_aux
        self._donate = donate_state
        self._accum = accumulation_steps
        self.plan = plan if plan is not None \
            else ShardingPlan.from_strategy(compiled_strategy, model_spec)
        self.mesh = mesh if mesh is not None else self._mesh_from_plan()
        # The mesh exists, so the backend is up: place the persistent
        # compile cache before this runner's first compile (no-op on CPU).
        compile_cache.configure()
        if self.zero and not self.plan.is_async and not self.plan.zero:
            # Synchronous regimes take the SPMD lowering; the async/PS regime
            # keeps its plan and shards the server-side apply instead
            # (parallel/staleness.py) — its opt state lives on the chief only.
            self.plan = self.plan.with_zero_update(self.mesh)
        # Uneven partitioning: state leaves live padded (XLA needs even tiles); the
        # user's loss fn sees logical shapes. Differentiating through the unpad
        # slice zero-fills the pad region of the gradient, so padded rows never
        # receive updates (the masked-update half of pad-and-mask).
        if self.plan.has_padding:
            unpad = self.plan.unpad_params
            self._step_loss_fn = lambda p, b: loss_fn(unpad(p), b)
        else:
            self._step_loss_fn = loss_fn
        self._grad_fn = synchronization.make_grad_fn(
            self.plan, model_spec, self.mesh, self._step_loss_fn, has_aux=has_aux)
        # Compiled steps keyed by fetch fn (None = plain step); reference cached
        # one built runner per graph the same way (autodist.py:280-287).
        self._step_fns: dict = {}
        self._many_fns: dict = {}   # fused K-step scans, same keying
        self._eval_fns: dict = {}
        self._state_shardings = None
        # Dispatch signatures (kind + fetch-fn token + batch shapes/dtypes)
        # already seen: a NEW signature means jit will retrace+recompile
        # inside the next call — the compile-telemetry key (_dispatch_span).
        # Fetch fns get a NEVER-REUSED token via a weak map: a bare id()
        # could be recycled by a new fn after the old one (evicted from the
        # step cache) is collected, silently suppressing its compile record.
        self._compile_sigs: set = set()
        self._mem_analysis_warned: set = set()
        # What the checkpointed layers of the step most lately traced keep on
        # a chip (noted at trace time, booked with the step's HBM account).
        self._kept_bytes = 0
        self._fetch_tokens: "weakref.WeakKeyDictionary" = \
            weakref.WeakKeyDictionary()
        self._fetch_token_next = 0

    def _mesh_from_plan(self) -> Mesh:
        axes = dict(self.plan.mesh_axes)
        n = len(jax.devices())
        if int(np.prod(list(axes.values()))) != n:
            # Strategy was built for a different device count (e.g. compiled on the
            # chief for the full pod, now dry-running on fewer chips): refill data,
            # then shrink the largest remaining axis until the product divides n.
            logging.warning(
                "Strategy mesh %s does not match %d local devices; refilling data axis",
                axes, n)
            axes.pop("data", None)
            axes = {a: s for a, s in axes.items() if s > 1}
            while axes and n % int(np.prod(list(axes.values()))) != 0:
                largest = max(axes, key=axes.__getitem__)
                axes.pop(largest)
            axes["data"] = -1
        return build_mesh(axes=axes)

    # ------------------------------------------------------------------- state
    def init(self, params: PyTree, rng: Optional[jax.Array] = None) -> TrainState:
        """Place initial state onto the mesh (reference ran initializers at session
        construction, runner.py:97-100). Params arrive at logical shapes; unevenly
        partitioned ones are zero-padded to their physical storage shape here.
        Every call books its seconds as ``setup.state_place_s`` and counts in
        ``setup.state_place_calls``, whether or not telemetry is on."""
        with telemetry.phase("setup.state_place_s"):
            state = self._place_state(params)
        telemetry.counter("setup.state_place_calls").inc()
        return state

    def _place_state(self, params: PyTree) -> TrainState:
        params = self.plan.pad_params(params)
        opt_state = self._optimizer.init(params)
        ef_state = synchronization.init_ef_state(self.plan, params, mesh=self.mesh)
        state = TrainState(step=np.zeros((), np.int32), params=params,
                           opt_state=opt_state, ef_state=ef_state, plan=self.plan)
        self._state_shardings = None   # rebuild for THIS init's trees
        self._ensure_state_shardings(state)
        # Jitted identity with out_shardings: places the state on the mesh AND
        # guarantees fresh buffers (a plain device_put may alias caller-owned arrays,
        # which step donation would then delete out from under the caller).
        place = jax.jit(lambda s: s, out_shardings=self._state_shardings)
        with self.mesh:
            return place(state)

    # -------------------------------------------------------------------- step
    def _make_step_body(self, fetch_fn: Optional[Callable] = None):
        """The pure (untraced) one-step function ``(state, batch) -> (state,
        (loss, aux, fetched, bundle))`` — ``bundle`` is the fused health
        numerics float32[4] when monitors are on, an empty tuple (nothing in
        the compiled program) when off. Single source of the step math:
        ``_build_step`` jits it directly and ``_build_many`` scans it — so the
        fused multi-step path can never drift numerically from the per-step
        path.

        The four phases of a step sit under ``jax.named_scope``s a device
        trace can read: ``step.grad`` (with ``step.grad_sync`` inside it, or
        after it under ZeRO), ``step.accumulate``, ``step.optimizer``. Names
        are trace-time metadata; the compiled arithmetic does not change."""
        import jax.numpy as jnp

        optimizer = self._optimizer
        grad_fn = self._grad_fn
        accum = self._accum
        # ZeRO update sharding: constraint points for the jitted step. Captured
        # as (plan, mesh) statics so the body stays a pure function of state.
        zero_plan = self.plan if self.plan.update_sharded else None
        mesh = self.mesh
        # Health bundle: a TRACE-TIME static — the disabled program carries
        # nothing (an empty tuple output), the enabled one a few fused
        # reductions over intermediates the step already has.
        health_on = self.health
        # The data shards one trace of the loss stands for: what divides the
        # bytes a trace counts of its own arrays into a chip's share.
        trace_shards = synchronization.batch_trace_shards(self.plan, mesh)

        def accumulate(params, batch, ef_state):
            """Gradient accumulation: scan grad_fn over the micro axis, summing
            gradients and threading error-feedback state; one optimizer update per
            outer step. Micro-batches are equal-sized, so the mean of per-micro
            (already data-synced) gradients equals the full-batch gradient for
            mean-reduced losses — value-exact vs one big batch."""
            def select(i):
                return jax.tree_util.tree_map(
                    lambda l: jax.lax.dynamic_index_in_dim(
                        l.value, i, axis=0, keepdims=False) if _is_micro(l) else l,
                    batch, is_leaf=_is_micro)

            def micro(carry, i):
                gsum, ef = carry
                with jax.named_scope("step.grad"):
                    grads, loss, aux, ef = grad_fn(params, select(i), ef)
                with jax.named_scope("step.accumulate"):
                    gsum = jax.tree_util.tree_map(jnp.add, gsum, grads)
                return (gsum, ef), (loss, aux)

            zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
            (gsum, ef_state), (losses, auxes) = jax.lax.scan(
                micro, (zeros, ef_state), jnp.arange(accum))
            with jax.named_scope("step.accumulate"):
                grads = jax.tree_util.tree_map(lambda g: g / accum, gsum)
            # Aux contraction matches the accum=1 shapes: per-example aux — leading
            # dim == the micro-batch size — folds back to [B, ...] (same examples,
            # same params, so values are identical to full-batch evaluation);
            # everything else (scalars, per-class vectors, ...) averages across
            # micros. A non-per-example aux whose leading dim happens to equal the
            # micro-batch size is indistinguishable and gets folded.
            micro_b = next((l.value.shape[1] for l in jax.tree_util.tree_leaves(
                batch, is_leaf=_is_micro) if _is_micro(l)), None)
            aux = jax.tree_util.tree_map(
                lambda a: a.reshape((-1,) + a.shape[2:])
                if a.ndim >= 2 and a.shape[1] == micro_b
                else jnp.mean(a, axis=0), auxes)
            return grads, jnp.mean(losses), aux, ef_state

        def step_fn(state: TrainState, batch: PyTree):
            # Trace time: what this step's checkpointed layers keep for their
            # backward (the gauge `remat.kept_bytes`, booked by the policy of
            # models/common.py `keeping` as this trace differentiates them)
            # is noted as a chip's share, the batch axis being split over
            # `data`, for the step's HBM account (`_dispatch_span`).
            kept = telemetry.registry().get("remat.kept_bytes")
            if kept is not None:
                kept.set(0)
            if accum > 1:
                grads, loss, aux, ef_state = accumulate(state.params, batch,
                                                        state.ef_state)
            else:
                with jax.named_scope("step.grad"):
                    grads, loss, aux, ef_state = grad_fn(state.params, batch,
                                                         state.ef_state)
            kept = telemetry.registry().get("remat.kept_bytes")
            # graftlint: disable=GL004(a Python int read from a host gauge: the note is of THIS trace by design — a later trace of the model elsewhere overwrites the gauge — and no traced value reaches it)
            self._kept_bytes = 0 if kept is None \
                else int(kept.value) // trace_shards
            if zero_plan is not None:
                # ZeRO weight-update sharding (arXiv 2004.13336): constraining
                # the gradient to the opt-state shards makes XLA materialize it
                # as a reduce-scatter; the optimizer update then runs on 1/dp
                # of each parameter per device.
                with jax.named_scope("step.grad_sync"):
                    grads = zero_plan.constrain_update(mesh, grads)
            with jax.named_scope("step.optimizer"):
                updates, opt_state = optimizer.update(grads, state.opt_state,
                                                      state.params)
                if zero_plan is not None:
                    updates = zero_plan.constrain_update(mesh, updates)
                    opt_state = zero_plan.constrain_opt(mesh, opt_state)
                params = optax.apply_updates(state.params, updates)
                if zero_plan is not None:
                    # Back to the storage sharding — the all-gather closing
                    # the sharded update.
                    params = zero_plan.constrain_params(mesh, params)
            new_state = TrainState(step=state.step + 1, params=params,
                                   opt_state=opt_state, ef_state=ef_state,
                                   plan=state.plan)
            # Arbitrary fetches (reference remapper.py:125-185 fetched any graph
            # tensor with per-kind contraction): computed in the same compiled
            # step from the pre-update params. SPMD supplies the contractions —
            # per-example outputs come back as the (logically concatenated)
            # global batch-sharded array, scalars as the replicated value the
            # reference took from the master replica.
            if fetch_fn is not None:
                # Fetches see the logical batch: micro-batched leaves fold back to
                # [B, ...] (row-major reshape restores the original example order).
                logical = jax.tree_util.tree_map(
                    lambda l: l.value.reshape((-1,) + l.value.shape[2:])
                    if _is_micro(l) else l,
                    batch, is_leaf=_is_micro)
                fetched = fetch_fn(state.params, logical)
            else:
                fetched = ()
            if health_on:
                from autodist_tpu.telemetry import health as _health
                # Pre-update params: the ratio convention is update magnitude
                # relative to the weights it applies to.
                bundle = _health.device_bundle(grads, updates, state.params,
                                               loss)
            else:
                bundle = ()   # empty pytree: nothing in the compiled program
            return new_state, (loss, aux, fetched, bundle)

        return step_fn

    def _cap_fn_cache(self, cache: dict, where: str):
        if len(cache) > 8:
            # Fetch callables are cache keys by identity: per-call lambdas would
            # recompile the full step every run and pin executables forever.
            evict = next(k for k in cache if k is not None)
            del cache[evict]
            logging.warning(
                "More than 8 distinct fetch callables compiled; pass a stable "
                "function to %s instead of per-call lambdas "
                "(each new identity recompiles the whole training step)", where)

    def _build_step(self, fetch_fn: Optional[Callable] = None):
        donate = (0,) if self._donate else ()
        step_fn = self._make_step_body(fetch_fn)
        # run, compiled_step and the cost probe all trace and lower this one
        # function: its stages are the set-up ledger's jit.step.*.
        compile_cache.register_step_programs(step_fn.__name__)
        jitted = jax.jit(
            step_fn,
            in_shardings=(self._state_shardings, None),
            out_shardings=(self._state_shardings, None),
            donate_argnums=donate,
        )
        self._step_fns[fetch_fn] = jitted
        self._cap_fn_cache(self._step_fns, "runner.run(fetches=...)")
        return jitted

    def _build_many(self, fetch_fn: Optional[Callable] = None):
        """Fused multi-step program: one ``lax.scan`` of the step body over a
        stacked batch block. Compiled once per (fetch fn, block length) — jit
        retraces per scan length, so varying block sizes (cadence-clipped tail
        blocks) reuse their own executables."""
        step_fn = self._make_step_body(fetch_fn)
        health_on = self.health

        def many_fn(state: TrainState, block: PyTree):
            state, (losses, auxes, fetched, bundles) = jax.lax.scan(
                step_fn, state, block)
            if health_on:
                # Reduce the [K, 4] per-step bundles ON DEVICE (nonfinite
                # sums, norms max) — a K-step block still reads back four
                # scalars at the log boundary.
                from autodist_tpu.telemetry import health as _health
                bundles = _health.reduce_bundle(bundles)
            return state, (losses, auxes, fetched, bundles)

        donate = (0,) if self._donate else ()
        compile_cache.register_step_programs(many_fn.__name__)
        jitted = jax.jit(
            many_fn,
            in_shardings=(self._state_shardings, None),
            out_shardings=(self._state_shardings, None),
            donate_argnums=donate,
        )
        self._many_fns[fetch_fn] = jitted
        self._cap_fn_cache(self._many_fns, "runner.run_many(fetches=...)")
        return jitted

    def _leading_dims(self, batch: PyTree):
        """Counter of leading dims over the batch's array leaves (MicroBatched
        leaves count at their logical ``k * micro`` size). The single
        shape-extraction rule shared by batch-dim inference and the explicit-
        batch_size sanity check, so the two cannot drift apart."""
        from collections import Counter
        dims: Counter = Counter()
        for leaf in jax.tree_util.tree_leaves(batch, is_leaf=_is_micro):
            if _is_micro(leaf):
                # Already laid out [k, B/k, ...] by a previous shard_batch.
                dims[leaf.value.shape[0] * leaf.value.shape[1]] += 1
                continue
            shape = getattr(leaf, "shape", None)
            if shape is None:
                shape = np.asarray(leaf).shape
            if len(shape) >= 1:
                dims[shape[0]] += 1
        return dims

    def _infer_batch_dim(self, dims, split: int) -> int:
        """The global batch size for micro-splitting: the explicit ``batch_size``
        if the runner was given one, else the unique splittable leading dim —
        provided it is also the most common one (the likeliest batch).

        There is no structural rule that can tell a batch leaf from an
        auxiliary leaf that happens to be splittable (sampled-softmax negatives
        longer than the batch, per-class vectors shorter than it — either can
        outnumber or outweigh the true batch leaves), and guessing wrong
        silently changes the loss. So anything other than the clean case — one
        splittable dim, and it is the modal one — refuses and asks for
        ``batch_size=``."""
        if self._batch_size is not None:
            return self._batch_size
        if not dims:
            return 0
        top = max(dims.values())
        modal = {d for d, c in dims.items() if c == top}
        splittable = sorted(d for d in dims if d % split == 0)
        if len(splittable) == 1 and modal == {splittable[0]}:
            return splittable[0]
        if len(splittable) > 1:
            raise ValueError(
                f"Ambiguous batch dimension for gradient accumulation: leading "
                f"dims {splittable} are all divisible by accumulation_steps*dp="
                f"{split}, and micro-splitting the wrong one would silently "
                f"change the loss; pass batch_size= to the runner (or "
                f"AutoDist.function / create_distributed_session) to pick one")
        if len(splittable) == 1:
            # The one splittable dim is NOT the most common leading dim: the
            # likeliest batch was excluded only by divisibility. Micro-splitting
            # the outlier would silently change the loss; make the user decide.
            raise ValueError(
                f"Cannot infer the batch dimension for gradient accumulation: "
                f"the only leading dim divisible by accumulation_steps*dp="
                f"{split} is {splittable[0]}, but the most common leading dim "
                f"is {sorted(modal)}; pass batch_size= (or make the batch "
                f"divisible) to pick one")
        # Nothing splittable: report against the most common leading dim (the
        # likeliest batch) so the divisibility error below names it.
        return max(modal)

    def _micro_batch_dim(self, batch: PyTree, k: int, dp: int) -> int:
        """The leading dim that micro-splits for accumulation (0 when off).
        Shared by shard_batch and shard_block so the per-step and fused paths
        can never infer different batch dims for the same runner."""
        if k <= 1:
            return 0
        dims = self._leading_dims(batch)
        batch_dim = self._infer_batch_dim(dims, k * dp)
        if batch_dim not in dims:
            # A typo'd explicit batch_size would otherwise silently disable
            # micro-splitting while the accumulation scan still runs k
            # identical full-batch micro-steps.
            raise ValueError(
                f"batch_size={batch_dim} matches no leaf's leading dim "
                f"(present: {sorted(dims)}); nothing would be "
                f"micro-split for accumulation_steps={k}")
        return batch_dim

    @staticmethod
    def _require_micro_divisible(n: int, k: int, dp: int):
        if n % (k * dp) != 0:
            raise ValueError(
                f"Global batch {n} is not divisible into "
                f"accumulation_steps={k} micro-batches over {dp} data "
                f"replicas; make it divisible by {k * dp} (or drop "
                f"accumulation)")

    def feed_layout(self) -> FeedLayout:
        """This runner's feed remapping as data (:class:`FeedLayout`) —
        the input-data plane's key for per-host sharded loading and
        prefetch placement (one layout source, shared with
        :meth:`shard_batch`/:meth:`shard_block`)."""
        return FeedLayout(mesh=self.mesh, plan=self.plan,
                          dp=synchronization.mesh_dp_size(self.mesh),
                          accum=self._accum)

    def shard_batch(self, batch: PyTree,
                    accumulation: Optional[int] = None) -> PyTree:
        """Feed remapping: split batch leaves across data replicas, duplicate the
        rest (reference remapper.py:81-123 semantics, with the polymorphic dim now
        'leading dim divisible by dp_size').

        With gradient accumulation (``accumulation_steps=k``), splittable leaves
        are additionally laid out ``[k, B/k, ...]`` (wrapped in ``MicroBatched``)
        so the compiled step can scan micro-batches; the reshape happens on the
        host, before placement, so it moves no device data. ``accumulation``
        overrides the runner's setting (evaluate() passes 1 — the micro layout
        only shapes the training scan)."""
        dp = synchronization.mesh_dp_size(self.mesh)
        k = self._accum if accumulation is None else accumulation

        # Which leaves are *batch* leaves for micro-splitting: those whose leading
        # dim equals the global batch size. The batch size is the modal (most
        # common) leading dim across the pytree, not the largest — an auxiliary
        # leaf longer than the batch (e.g. sampled-softmax negatives with
        # num_sampled > batch_size) must NOT be mistaken for the batch, or each
        # micro-step would see the full batch with a slice of the negatives.
        # Ambiguity (two splittable dims equally common) raises rather than
        # guessing; ``batch_size=`` on the runner resolves it explicitly.
        batch_dim = self._micro_batch_dim(batch, k, dp)

        def put(leaf):
            if _is_micro(leaf):
                return leaf  # already laid out by a previous shard_batch
            shape = getattr(leaf, "shape", None)
            if shape is None:
                leaf = np.asarray(leaf)
                shape = leaf.shape
            if k > 1 and len(shape) >= 1 and shape[0] == batch_dim:
                self._require_micro_divisible(shape[0], k, dp)
                micro = leaf.reshape((k, shape[0] // k) + tuple(shape[1:]))
                spec = P(None, *self.plan.batch_pspec(len(shape)))
                return MicroBatched(
                    place_host_value(micro, NamedSharding(self.mesh, spec)))
            if len(shape) >= 1 and shape[0] % dp == 0:
                spec = self.plan.batch_pspec(len(shape))
            else:
                spec = P()
            sharding = NamedSharding(self.mesh, spec)
            if isinstance(leaf, jax.Array) and leaf.sharding == sharding:
                return leaf  # already resident with the right layout — no transfer
            return place_host_value(leaf, sharding)

        return jax.tree_util.tree_map(put, batch, is_leaf=_is_micro)

    def shard_block(self, batches) -> BatchBlock:
        """Stack K host batches into one on-device :class:`BatchBlock` for
        :meth:`run_many`.

        The feed remapping is ``shard_batch``'s, shifted one axis right: every
        leaf gains a leading (unsharded) step axis of length K, batch leaves
        shard their *second* dim over the data axes, non-batch leaves
        replicate, and micro-batched leaves (gradient accumulation) lay out
        ``[K, accum, B/accum, ...]``. Stacking happens on the host before one
        placement per leaf, so a block costs the same number of host->device
        transfers as a single batch."""
        batches = list(batches)
        if not batches:
            raise ValueError("shard_block needs at least one batch")
        treedef = jax.tree_util.tree_structure(batches[0], is_leaf=_is_micro)
        for i, b in enumerate(batches[1:], 1):
            td = jax.tree_util.tree_structure(b, is_leaf=_is_micro)
            if td != treedef:
                raise ValueError(
                    f"shard_block: batch {i}'s pytree structure {td} does not "
                    f"match batch 0's {treedef}; a block scans one compiled "
                    f"step over uniformly-shaped batches")
        K = len(batches)
        dp = synchronization.mesh_dp_size(self.mesh)
        k = self._accum
        batch_dim = self._micro_batch_dim(batches[0], k, dp)

        def put(*leaves):
            import jax.numpy as jnp
            # Device-resident leaves (HBM-cached records, re-fed fetches) stack
            # on-device: stack/reshape dispatch asynchronously and device_put
            # relayouts without the host round-trip np.asarray would force —
            # the block analogue of shard_batch's already-resident fast path.
            # Mixed host/device leaves fall back to host stacking.
            resident = all(isinstance(l.value if _is_micro(l) else l, jax.Array)
                           for l in leaves)
            xp = jnp if resident else np
            arrs = []
            for leaf in leaves:
                if _is_micro(leaf):
                    # Fold a pre-sharded [k, B/k, ...] layout back to logical.
                    v = leaf.value if resident else np.asarray(leaf.value)
                    leaf = v.reshape((-1,) + v.shape[2:])
                arrs.append(leaf if resident else np.asarray(leaf))
            shape = tuple(arrs[0].shape)
            ragged = {tuple(a.shape) for a in arrs}
            if len(ragged) > 1:
                # The per-step path tolerates shape drift by recompiling; a
                # block scans ONE compiled step, so name the problem instead
                # of letting stack() raise a bare shape error mid-training.
                raise ValueError(
                    f"shard_block: batches disagree on a leaf's shape "
                    f"{sorted(ragged)}; a fused block scans one compiled step "
                    f"over uniformly-shaped batches — pad the ragged batch "
                    f"(or use unroll=1 / per-step run() for shape-bucketed "
                    f"data)")
            stacked = xp.stack(arrs)

            def place(value, spec):
                sharding = NamedSharding(self.mesh, spec)
                if resident:
                    return jax.device_put(value, sharding)
                return place_host_value(value, sharding)

            if k > 1 and len(shape) >= 1 and shape[0] == batch_dim:
                self._require_micro_divisible(shape[0], k, dp)
                micro = stacked.reshape((K, k, shape[0] // k) + shape[1:])
                return MicroBatched(place(
                    micro, P(None, None, *self.plan.batch_pspec(len(shape)))))
            if len(shape) >= 1 and shape[0] % dp == 0:
                spec = P(None, *self.plan.batch_pspec(len(shape)))
            else:
                spec = P()
            return place(stacked, spec)

        tree = jax.tree_util.tree_map(put, *batches, is_leaf=_is_micro)
        return BatchBlock(tree, K)

    def _fetch_token(self, fetch_fn) -> str:
        """A stable, never-reused token for a fetch fn (monotonic counter
        behind a weak map — a collected fn's token is never handed to a new
        one, unlike a recycled ``id()``)."""
        if fetch_fn is None:
            return "-"
        try:
            token = self._fetch_tokens.get(fetch_fn)
            if token is None:
                self._fetch_token_next += 1
                token = self._fetch_tokens[fetch_fn] = self._fetch_token_next
        except TypeError:          # non-weakref-able callable: best effort
            return f"id{id(fetch_fn)}"
        return str(token)

    def _compile_signature(self, kind: str, fetch_fn, batch: PyTree) -> str:
        """Shape signature of one dispatch: the (kind, fetch-fn token,
        per-leaf dtype/shape, treedef) tuple jit keys its executable cache
        by, flattened to a string. Two calls with equal signatures hit the
        same compiled program; a fresh signature recompiles — which is what
        the compile telemetry counts."""
        parts = [kind, self._fetch_token(fetch_fn)]
        leaves, treedef = jax.tree_util.tree_flatten(batch, is_leaf=_is_micro)
        parts.append(str(treedef))
        for leaf in leaves:
            v = leaf.value if _is_micro(leaf) else leaf
            parts.append(f"{'m' if _is_micro(leaf) else ''}"
                         f"{getattr(v, 'dtype', type(v).__name__)}"
                         f"{getattr(v, 'shape', ())}")
        return "|".join(parts)

    def compiled_step(self, state: TrainState, sharded_batch: PyTree):
        """The compiled plain-step executable at these args (``as_text()``,
        ``cost_analysis()``, ``memory_analysis()``). After the step has run
        once at this signature the re-lowering hits jit's executable cache;
        before, this is the compile."""
        fn = self._step_fns.get(None) or self._build_step(None)
        with self.mesh:
            return fn.lower(state, sharded_batch).compile()

    def _extract_program_cost(self, jitted, args, steps: int = 1):
        """XLA's static cost analysis for ``jitted`` at ``args`` as a plain
        ``{"flops", "bytes_accessed", "output_bytes"}`` dict, or None when
        the backend reports nothing. Called right after the first dispatch
        of a signature compiled, so ``lower().compile()`` hits the
        executable cache (the same contract ``utils/flops.train_step_flops``
        relies on); accounting must never break a step, hence the broad
        guard.

        ``steps`` scales flops/bytes for the fused K-step block program:
        HloCostAnalysis visits each instruction ONCE and does not model
        loop trip counts, so a ``lax.scan``-of-K-steps program reports its
        body's cost, not K of them — the runner knows K and restores it
        (verified on this backend: the K=4 block reports ~1x the
        single-step program's flops). The gradient-accumulation scan inside
        the step body (``accumulate``'s micro loop) is the same shape of
        under-count, so ``self._accum`` scales too — a slight over-count of
        the once-per-step optimizer update, accepted because the gradient
        pass dominates any program accumulation is worth using on."""
        try:
            # Once a program (the first dispatch of a signature with the
            # profiling plane armed, or the autotuner's probe): what the
            # re-lowering and the analysis cost goes to the set-up ledger.
            with telemetry.phase("setup.cost_probe_s"):
                with self.mesh:
                    compiled = jitted.lower(*args).compile()
                cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else None
            if not cost:
                return None
            k = max(1, int(steps)) * max(1, int(self._accum))
            # Backends report -1 for properties they don't know (the same
            # sentinel utils/flops._flops_from_cost guards): only POSITIVE
            # counts are real.
            flops = float(cost.get("flops", 0.0) or 0.0)
            bytes_acc = float(cost.get("bytes accessed", 0.0) or 0.0)
            if flops <= 0:
                return None
            out: dict = {"flops": k * flops,
                         "bytes_accessed":
                             k * bytes_acc if bytes_acc > 0 else None}
            # The memory ledger: the full memory_analysis() record (bytes a
            # dispatch pins while running — UNscaled by k: the block's
            # working set does not multiply with its trip count). Optional
            # on some backends, but named when absent — a silently-None
            # ledger is how the memory plane goes dark.
            out.update(dict.fromkeys(_profiling.MEMORY_FIELDS),
                       **self._compiled_memory(compiled))
            return out
        except Exception:  # noqa: BLE001
            return None

    def _compiled_memory(self, compiled) -> dict:
        """A compiled program's ``memory_analysis()`` as a
        ``telemetry.profiling.MEMORY_FIELDS`` dict of a device's bytes
        (``alias_bytes``: the outputs that live in donated arguments), empty
        where the backend has none."""
        try:
            mem = compiled.memory_analysis()
            return {"argument_bytes": int(mem.argument_size_in_bytes),
                    "output_bytes": int(mem.output_size_in_bytes),
                    "temp_bytes": int(mem.temp_size_in_bytes),
                    "alias_bytes": int(mem.alias_size_in_bytes),
                    "generated_code_bytes":
                        int(mem.generated_code_size_in_bytes)}
        except Exception as e:  # noqa: BLE001 — optional on some backends
            backend = jax.default_backend()
            if backend not in self._mem_analysis_warned:
                self._mem_analysis_warned.add(backend)
                logging.debug(
                    "memory_analysis() unavailable on the %r backend "
                    "(%s); the per-program memory ledger will be empty",
                    backend, e)
            return {}

    def _step_memory(self, jitted, args) -> dict:
        """The HBM account's once-a-signature reading: ``memory_analysis()``
        of the step as it was just dispatched. ``args`` are the dispatch's
        arguments as shapes and shardings, so the trace and the lowering are
        the call path's cached ones and the executable is the one that ran:
        nothing is asked of the backend. Never breaks a step."""
        try:
            with self.mesh:
                return self._compiled_memory(jitted.lower(*args).compile())
        except Exception as e:  # noqa: BLE001
            logging.debug("step memory account unavailable: %s", e)
            return {}

    def _maybe_record_oom(self, where: str, exc: BaseException) -> None:
        """OOM forensics at the dispatch sites: when a step died of
        RESOURCE_EXHAUSTED, book the ``mem.oom`` event and trigger the
        (debounced) flight recorder — whose manifest ``memory`` section is
        the autopsy: census, program ledger, predicted-vs-live peak. The
        caller re-raises the real error either way; forensics never mask
        it (and never fire on non-memory failures)."""
        try:
            from autodist_tpu.telemetry import memplane as _memplane
            if _memplane.is_oom_error(exc):
                _memplane.record_oom(where, exc)
        except Exception:  # noqa: BLE001 — diagnostics must never mask
            pass

    def _dispatch_span(self, name: str, kind: str, fetch_fn, batch: PyTree,
                       cost_probe=None, **span_args):
        """The span wrapping a compiled-step dispatch. Enabled mode only: the
        first dispatch of a NEW shape signature becomes a ``jit.compile``
        span (carrying a crc32 of the signature) whose exit books
        ``jit.cache_miss``/``jit.compile_s`` — so "why was step N slow"
        answers itself as "a new batch shape recompiled". Every dispatch
        additionally counts against its signature's
        :class:`telemetry.profiling.ProgramCost` record, and — with the
        profiling plane active — the first dispatch pulls the compiled
        program's XLA cost analysis through ``cost_probe`` (the jitted fn
        plus its args) into that record. Either span sits inside a
        ``StepTraceAnnotation`` (:class:`_StepAnnotated`). Disabled mode
        short-circuits to the shared no-op span."""
        if not telemetry.enabled():
            return telemetry.span(name)
        sig = self._compile_signature(kind, fetch_fn, batch)
        digest = format(zlib.crc32(sig.encode()), "08x")
        steps = int(span_args.get("steps", 1))
        step_num = self._annotated_steps
        self._annotated_steps += steps
        _profiling.note_dispatch(digest, kind, steps)
        if sig in self._compile_sigs:
            return _StepAnnotated(telemetry.span(name, **span_args), step_num)
        self._compile_sigs.add(sig)
        cost_cb = None
        if cost_probe is not None:
            jitted, jit_args = cost_probe
            # Shapes and shardings, taken while the donated state is alive.
            jit_args = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=x.sharding)
                if isinstance(x, jax.Array) else x, jit_args)
            with_cost = _profiling.active()

            def cost_cb(compile_s, _d=digest, _k=kind, _s=steps,
                        _fn=jitted, _a=jit_args):
                # The step's own HBM account (telemetry/memplane.py), and
                # with the profiling plane on XLA's cost analysis beside it.
                from autodist_tpu.telemetry import memplane as _memplane
                t0 = time.perf_counter()
                cost = None
                if with_cost:
                    cost = self._extract_program_cost(_fn, _a, steps=_s)
                    _profiling.record_program_cost(_d, _k, _s, cost,
                                                   compile_s=compile_s)
                memory = cost or self._step_memory(_fn, _a)
                _profiling.record_program_memory(_d, memory)
                _memplane.book_step_hbm(memory, time.perf_counter() - t0,
                                        kept_bytes=self._kept_bytes)
        return _StepAnnotated(_CompileProbe(telemetry.span(
            "jit.compile", kind=kind, sig=digest, **span_args), cost_cb),
            step_num)

    # ------------------------------------------------- compile-only cost probe
    def _abstract_state(self, params: PyTree) -> TrainState:
        """The :class:`TrainState` this runner's ``init(params)`` would build,
        as a ``ShapeDtypeStruct`` pytree via ``jax.eval_shape`` — no device
        allocation, no dispatch. The probe path's stand-in for real state."""
        import jax.numpy as jnp

        def build(p):
            p = self.plan.pad_params(p)
            opt_state = self._optimizer.init(p)
            ef_state = synchronization.init_ef_state(self.plan, p)
            return TrainState(step=jnp.zeros((), jnp.int32), params=p,
                              opt_state=opt_state, ef_state=ef_state,
                              plan=self.plan)

        return jax.eval_shape(build, params)

    def _ensure_state_shardings(self, state: TrainState):
        """Derive the jit in/out shardings from a (possibly abstract) state
        tree — the ONE sharding-tree construction, shared by ``init``
        (concrete trees) and the compile-only probe (ShapeDtypeStructs; the
        derivation only reads leaf paths), so the probe can never lower a
        program with different shardings than the real run."""
        if self._state_shardings is not None:
            return
        # State stored as shares over the data axis (strategy.FullySharded):
        # what one gather of every such parameter brings a device, and one
        # reduce-scatter of every such gradient takes from it, from the plan.
        moved = self.plan.data_shard_bytes(
            self._model_spec, synchronization.mesh_dp_size(self.mesh))
        if moved:
            telemetry.gauge("step.param_gather_bytes").set(moved)
            telemetry.gauge("step.grad_scatter_bytes").set(moved)
        self._state_shardings = TrainState(
            step=NamedSharding(self.mesh, P()),
            params=self.plan.param_sharding_tree(self.mesh, state.params),
            opt_state=self.plan.opt_sharding_tree(self.mesh, state.opt_state),
            ef_state=synchronization.ef_sharding_tree(self.mesh,
                                                      state.ef_state),
            plan=self.plan)

    def _abstract_batch(self, batch: PyTree, block: int = 0) -> PyTree:
        """The ShapeDtypeStruct mirror of ``shard_batch`` (``block=0``) /
        ``shard_block`` (``block=K``)'s layout — same micro-batch wrapping and
        leading axes, no placement. Feeds :meth:`plan_costs`' lowering."""
        dp = synchronization.mesh_dp_size(self.mesh)
        k = self._accum
        batch_dim = self._micro_batch_dim(batch, k, dp)

        def abs_leaf(leaf):
            micro = _is_micro(leaf)
            if micro:
                leaf = leaf.value           # already laid out [k, B/k, ...]
            arr = leaf if hasattr(leaf, "shape") else np.asarray(leaf)
            shape, dtype = tuple(arr.shape), np.dtype(arr.dtype)
            if (not micro and k > 1 and len(shape) >= 1
                    and shape[0] == batch_dim):
                self._require_micro_divisible(shape[0], k, dp)
                shape = (k, shape[0] // k) + shape[1:]
                micro = True
            if block:
                shape = (block,) + shape
            struct = jax.ShapeDtypeStruct(shape, dtype)
            return MicroBatched(struct) if micro else struct

        return jax.tree_util.tree_map(abs_leaf, batch, is_leaf=_is_micro)

    def plan_costs(self, params: PyTree, example_batch: PyTree,
                   unroll: int = 1) -> Optional[dict]:
        """Compile-only static cost probe of this runner's step program.

        Lowers + compiles the (``unroll=K`` fused or single-step) training
        program at abstract args — state via :meth:`_abstract_state`, batch
        via :meth:`_abstract_batch` — and returns XLA's cost analysis as a
        ``{"flops", "bytes_accessed", "output_bytes", "steps", "dispatches",
        "source"}`` record (flops/bytes PER DISPATCH, the shape
        ``telemetry.costmodel.predict`` consumes), or None when the backend
        reports nothing. **No step executes and no state is allocated**: the
        probe's only cost is one compilation, which lands in jit's executable
        cache so a later real first step of the same signature reuses it.
        This is the predict-stage interface the plan autotuner
        (:mod:`autodist_tpu.strategy.autotune`) ranks candidates with."""
        if unroll < 1:
            raise ValueError("unroll must be >= 1")
        if unroll > 1 and not self.supports_run_many:
            raise RuntimeError(
                f"{type(self).__name__} has no fused multi-step program to "
                f"probe at unroll={unroll}; probe unroll=1")
        state = self._abstract_state(params)
        self._ensure_state_shardings(state)
        if unroll > 1:
            jitted = self._many_fns.get(None)
            if jitted is None:
                jitted = self._build_many(None)
            batch = self._abstract_batch(example_batch, block=unroll)
        else:
            jitted = self._step_fns.get(None)
            if jitted is None:
                jitted = self._build_step(None)
            batch = self._abstract_batch(example_batch)
        cost = self._extract_program_cost(jitted, (state, batch), steps=unroll)
        if cost is None:
            return None
        return dict(cost, steps=unroll, dispatches=1, source="xla")

    def logical_params(self, state_or_params) -> PyTree:
        """The parameter tree at its original (user-facing, unpadded) shapes."""
        params = state_or_params.params if isinstance(state_or_params, TrainState) \
            else state_or_params
        return self.plan.unpad_params(params)

    def run(self, state: TrainState, batch: PyTree,
            fetches: Optional[Callable] = None) -> Tuple[TrainState, Any]:
        """One synchronized training step. Returns ``(new_state, fetched)``.

        ``fetched`` defaults to the loss (or ``(loss, aux)`` with has_aux). With
        ``fetches=fn`` — any ``fn(params, batch) -> pytree`` — it becomes
        ``(default_fetches, fn_result)``, computed inside the same compiled step
        from the pre-update parameters (the reference fetched arbitrary session
        tensors the same way, remapper.py:125-185). Per-example leaves return as
        global batch-sharded arrays (the concat contraction); scalars return
        replicated (the master-replica contraction).
        """
        if self._state_shardings is None:
            raise RuntimeError("Call init(params) before run()")
        step_fn = self._step_fns.get(fetches)
        first_build = step_fn is None
        if first_build:
            step_fn = self._build_step(fetches)
        with telemetry.span("runner.shard_batch"):
            sharded = self.shard_batch(batch)
        if first_build and not self._step_fns.keys() - {fetches}:
            self._maybe_dump_graphs(state, sharded, step_fn)
        # The dispatch span closes when the program is ENQUEUED (dispatch is
        # asynchronous); the wait for results shows up in the caller's
        # readback span (metrics._sync / device_get), and device execution in
        # the jax.profiler trace. A long dispatch span means compilation or a
        # full dispatch queue — and the first dispatch of a new shape
        # signature is recorded AS compilation (jit.compile span +
        # jit.cache_miss/jit.compile_s counters, see _dispatch_span).
        try:
            with self._dispatch_span("runner.run.dispatch", "step", fetches,
                                     sharded, cost_probe=(step_fn,
                                                          (state, sharded))):
                with self.mesh:
                    new_state, (loss, aux, fetched, bundle) = step_fn(state,
                                                                      sharded)
        except Exception as e:  # noqa: BLE001 — OOM forensics, then re-raise
            self._maybe_record_oom("runner.run", e)
            raise
        if self.health:
            self.last_health = bundle
        default = (loss, aux) if self._has_aux else loss
        if fetches is not None:
            return new_state, (default, fetched)
        return new_state, default

    def run_many(self, state: TrainState, batches,
                 fetches: Optional[Callable] = None) -> Tuple[TrainState, Any]:
        """K fused training steps in ONE compiled dispatch.

        ``batches`` is a sequence of K host batches, or a pre-sharded
        :class:`BatchBlock` from :meth:`shard_block` /
        ``device_prefetch(unroll=K)``. The step body is scanned on-device, so
        Python dispatch, feed remapping, and fetch materialization are paid
        once per K steps — and the result is bit-identical to K sequential
        :meth:`run` calls (same body, same shardings; test-pinned).

        The fetch contract is :meth:`run`'s with a leading ``[K]`` step axis:
        losses return as a ``[K]`` stack, aux and ``fetches=fn`` results stack
        per step (each slice computed from that step's pre-update params)."""
        if not self.supports_run_many:
            raise RuntimeError(
                f"{type(self).__name__} does not support run_many: the async "
                f"regime's parameter service applies gradients step-by-step; "
                f"use run() (or train(..., unroll=1))")
        if self._state_shardings is None:
            raise RuntimeError("Call init(params) before run_many()")
        if isinstance(batches, BatchBlock):
            block = batches
        else:
            with telemetry.span("runner.shard_block"):
                block = self.shard_block(batches)
        many_fn = self._many_fns.get(fetches)
        if many_fn is None:
            many_fn = self._build_many(fetches)
        try:
            with self._dispatch_span("runner.run_many.dispatch", "many",
                                     fetches, block.tree, steps=block.length,
                                     cost_probe=(many_fn,
                                                 (state, block.tree))):
                with self.mesh:
                    new_state, (losses, auxes, fetched, bundle) = many_fn(
                        state, block.tree)
        except Exception as e:  # noqa: BLE001 — OOM forensics, then re-raise
            self._maybe_record_oom("runner.run_many", e)
            raise
        if self.health:
            self.last_health = bundle
        default = (losses, auxes) if self._has_aux else losses
        if fetches is not None:
            return new_state, (default, fetched)
        return new_state, default

    def evaluate(self, state: TrainState, batch: PyTree,
                 fn: Optional[Callable] = None):
        """Forward-only compiled evaluation — no gradients, no update, no
        donation; ``state`` stays valid and unchanged.

        ``fn(params, batch) -> pytree`` defaults to the loss function. Params
        are presented at logical (unpadded) shapes, like the training step.
        The reference evaluated by session-running non-train fetches
        (remapper.py:125-185 master-replica contraction); here it is its own
        tiny compiled program, cached per ``fn`` identity.
        """
        if self._state_shardings is None:
            raise RuntimeError("Call init(params) before evaluate()")
        fn = fn if fn is not None else self._loss_fn
        jitted = self._eval_fns.get(fn)
        if jitted is None:
            unpad = self.plan.unpad_params if self.plan.has_padding else (lambda t: t)
            jitted = jax.jit(lambda p, b: fn(unpad(p), b),
                             in_shardings=(self._state_shardings.params, None))
            self._eval_fns[fn] = jitted
            if len(self._eval_fns) > 8:
                # Never evict the default (loss) entry — it is the hot path.
                evict = next(k for k in self._eval_fns if k is not self._loss_fn)
                del self._eval_fns[evict]
                logging.warning(
                    "More than 8 distinct evaluate() callables compiled; pass a "
                    "stable function instead of per-call lambdas")
        # A batch pre-sharded for an accumulating run() carries MicroBatched
        # [k, B/k, ...] leaves — fold them back to the logical layout first.
        batch = jax.tree_util.tree_map(
            lambda l: l.value.reshape((-1,) + l.value.shape[2:]) if _is_micro(l)
            else l, batch, is_leaf=_is_micro)
        sharded = self.shard_batch(batch, accumulation=1)
        with self.mesh:
            return jitted(state.params, sharded)

    def _maybe_dump_graphs(self, state: TrainState, sharded_batch: PyTree,
                           step_fn: Callable):
        """Stage snapshots (reference dumped the graph at each transform stage,
        graph_transformer.py:62-90): 0-original = the user's loss fn, 1-distributed
        = the sharded train step. ``sharded_batch`` is already on-device."""
        from autodist_tpu import const
        if not const.ENV.AUTODIST_DUMP_GRAPHS.val:
            return
        from autodist_tpu.utils import tracing
        # The user's loss fn sees the logical batch: fold micro-batched leaves back.
        logical_batch = jax.tree_util.tree_map(
            lambda l: l.value.reshape((-1,) + l.value.shape[2:]) if _is_micro(l)
            else l, sharded_batch, is_leaf=_is_micro)
        with self.mesh:
            tracing.dump_stage("train_step", "0-original", self._step_loss_fn,
                               state.params, logical_batch)
            tracing.dump_stage("train_step", "1-distributed",
                               lambda s, b: step_fn(s, b), state, sharded_batch)

    # Convenience parity alias: session.run(...)
    __call__ = run
