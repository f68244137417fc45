"""User API: the AutoDist class.

Surface parity with reference ``autodist/autodist.py``:

- ``AutoDist(resource_spec_file, strategy_builder)`` with PSLoadBalancing as the
  default builder (reference ``autodist.py:70``).
- ``scope()`` context manager around single-device model code (``:309-322``). In JAX
  nothing needs monkey patching (the reference patched optimizers/Keras inside the
  scope, ``patch.py``); the scope sets the process-default instance and marks the
  capture phase.
- ``build_strategy()`` / the chief-build-or-worker-load handshake keyed by
  ``AUTODIST_STRATEGY_ID`` (``:100-109``) — the serialized strategy is what ships to
  worker processes.
- ``create_distributed_session(...)`` -> :class:`DistributedRunner` (``:191-198``).
- ``function(...)`` -> a cached step callable (``:269-289``), the TF2-style path the
  lm1b example uses.
"""

import contextlib
from typing import Any, Callable, Optional, Sequence, Union

from autodist_tpu import const, telemetry
from autodist_tpu.model_spec import ModelSpec
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.runner import DistributedRunner
from autodist_tpu.strategy.base import Strategy, StrategyBuilder, StrategyCompiler
from autodist_tpu.utils import logging

_default_autodist = None


def set_default_autodist(ad: "AutoDist"):
    global _default_autodist
    _default_autodist = ad


def get_default_autodist() -> Optional["AutoDist"]:
    return _default_autodist


class AutoDist:
    """Entry point: resource spec + strategy builder -> distributed execution."""

    def __init__(self, resource_spec_file: Union[str, ResourceSpec, None] = None,
                 strategy_builder: Union[StrategyBuilder, str, None] = None):
        """``resource_spec_file``: YAML path, inline YAML text, an already-parsed
        :class:`ResourceSpec`, or None for the local-devices default.

        ``strategy_builder``: a builder instance, or the string
        ``"autotune"`` — the first ``create_distributed_session`` then runs
        the plan autotuner (:mod:`autodist_tpu.strategy.autotune`) and
        applies the winning builder + execution knobs (warm plan-cache
        launches skip the search entirely)."""
        from autodist_tpu.strategy import PSLoadBalancing
        if isinstance(resource_spec_file, ResourceSpec):
            self._resource_spec = resource_spec_file
        else:
            self._resource_spec = ResourceSpec(resource_spec_file)
        self._autotune = False
        if isinstance(strategy_builder, str):
            if strategy_builder != "autotune":
                raise ValueError(
                    f"unknown strategy name {strategy_builder!r}; the only "
                    f"string strategy is 'autotune' (pass a StrategyBuilder "
                    f"instance otherwise)")
            self._autotune = True
            strategy_builder = None
        self._tuned_plan = None
        self._strategy_builder = strategy_builder or PSLoadBalancing()
        self._strategy: Optional[Strategy] = None
        self._compiled: Optional[Strategy] = None
        self._model_signature = None
        self._cluster = None
        self._coordinator = None
        set_default_autodist(self)

    @property
    def resource_spec(self) -> ResourceSpec:
        return self._resource_spec

    @property
    def is_chief(self) -> bool:
        """Chief/worker role split via AUTODIST_WORKER env (reference autodist.py:40-41)."""
        return not const.ENV.AUTODIST_WORKER.val

    @contextlib.contextmanager
    def scope(self):
        """Graph-capture scope (reference autodist.py:309-322). In JAX the model code
        inside needs no rewriting; the scope installs this instance as the process
        default so library code can find it."""
        prev = get_default_autodist()
        set_default_autodist(self)
        try:
            yield self
        finally:
            set_default_autodist(prev)

    # ----------------------------------------------------------------- strategy
    def build_strategy(self, model_spec: ModelSpec) -> Strategy:
        """Build (chief) or load (worker) the strategy (reference autodist.py:91-109)."""
        if self._strategy is not None:
            return self._strategy
        # Once a build: its seconds go to the registry whether or not
        # telemetry is on (set-up is paid on every fresh machine and restart).
        with telemetry.phase("setup.strategy_build_s"):
            if self.is_chief:
                self._strategy = self._strategy_builder.build(
                    model_spec, self._resource_spec)
                with telemetry.phase("setup.strategy_write_s"):
                    path = self._strategy.serialize()
                    logging.info("Built strategy %s -> %s",
                                 self._strategy.id, path)
            else:
                strategy_id = const.ENV.AUTODIST_STRATEGY_ID.val
                if not strategy_id:
                    raise RuntimeError(
                        "Worker process has no AUTODIST_STRATEGY_ID; the "
                        "coordinator must ship the chief's strategy id")
                self._strategy = Strategy.deserialize(strategy_id)
                logging.info("Loaded strategy %s (worker)", strategy_id)
        return self._strategy

    def _compile(self, model_spec: ModelSpec) -> Strategy:
        # One model per AutoDist instance, like the reference's single cached graph
        # (autodist.py:280-287): reusing a strategy built for a different model would
        # silently mis-distribute it, so that is an error.
        signature = tuple(sorted((n, p.shape) for n, p in model_spec.trainable.items()))
        if self._compiled is not None and signature != self._model_signature:
            raise RuntimeError(
                "This AutoDist instance already compiled a strategy for a different "
                "model; create a new AutoDist per model (one-model-per-instance, as "
                "in the reference)")
        if self._compiled is None:
            strategy = self.build_strategy(model_spec)
            self._compiled = StrategyCompiler(model_spec, self._resource_spec).compile(strategy)
            self._model_signature = signature
        return self._compiled

    # ------------------------------------------------------------------ session
    def _setup(self, strategy, async_mode: bool):
        """Multi-node setup on first session creation (reference autodist.py:120-128).

        Synchronous strategies: every process joins one jax.distributed SPMD
        program. Non-synchronous (async / bounded-stale PS) strategies: processes
        stay independent JAX programs joined only by the chief's parameter-service
        transport — the reference's async workers were likewise joined only by the
        grpc PS plane, never by collectives."""
        if self._cluster is not None or self._resource_spec.num_nodes <= 1:
            return
        from autodist_tpu.cluster import Cluster
        from autodist_tpu.coordinator import Coordinator
        from autodist_tpu.parallel.multihost import maybe_initialize_multihost
        self._cluster = Cluster(self._resource_spec)
        self._cluster.start()
        if self.is_chief:
            self._coordinator = Coordinator(strategy, self._cluster)
            extra_env = None
            if async_mode:
                # Reserve the PS transport port NOW (the server itself starts
                # after runner.init): binding before shipping the address means
                # workers never connect to a guessed, possibly-taken port.
                import socket as _socket
                host = self._resource_spec.chief_address
                sock = _socket.socket()
                sock.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1)
                sock.bind((host, 0))
                self._ps_listen_sock = sock
                self._ps_address = f"{host}:{sock.getsockname()[1]}"
                extra_env = {const.ENV.AUTODIST_PS_ADDR.name: self._ps_address}
            self._coordinator.launch_clients(extra_env=extra_env)
        if not async_mode:
            maybe_initialize_multihost(self._cluster)
        import atexit
        atexit.register(self._teardown)

    def _teardown(self):
        """Teardown ordering parity (reference autodist.py:178-183): coordinator
        join (bounded — an abnormal chief exit must not deadlock on workers stuck in
        a collective), then cluster terminate."""
        try:
            if self._coordinator is not None:
                self._coordinator.join(timeout=10.0)
        finally:
            session = getattr(self, "_session", None)
            if session is not None and hasattr(session, "close"):
                session.close()
            if self._cluster is not None:
                self._cluster.terminate()

    def create_distributed_session(self, loss_fn: Callable, params: Any, optimizer,
                                   example_batch: Any = None,
                                   sparse_names: Optional[Sequence[str]] = None,
                                   has_aux: bool = False,
                                   num_workers: Optional[int] = None,
                                   accumulation_steps: int = 1,
                                   batch_size: Optional[int] = None,
                                   zero: Optional[Any] = None,
                                   health: Optional[bool] = None,
                                   tune: Optional[bool] = None) -> DistributedRunner:
        """Compile the strategy for this model and return the runner
        (reference autodist.py:191-198 returned the wrapped session).

        Strategies requesting a non-synchronous PS regime (``sync=False`` or
        ``staleness>0``) return the host-driven :class:`AsyncPSRunner` instead of the
        SPMD runner — the reference switched regimes inside PSSynchronizer
        (``ps_synchronizer.py:335-458``); here the regime selects the runner.
        ``num_workers`` sizes the async worker pool. Default: one slot per
        launched process on a multi-node cluster (slot 0 = the chief's drop-in
        ``run()``; each worker process steps its own slot over the PS transport),
        or a single slot on single-node runs — an in-process phantom worker that
        never steps would deadlock the staleness gate. Pass it explicitly when
        driving multiple in-process worker handles.

        ``zero`` enables ZeRO-style weight-update sharding (default: the
        ``AUTODIST_ZERO`` flag): the synchronous runner shards optimizer state
        and the update over the data-parallel axes (reduce-scatter ->
        shard-local update -> all-gather); the async regime shards the chief's
        server-side apply over N concurrent param shards (``zero=N``). See
        docs/usage/performance.md "Weight-update sharding (ZeRO)".

        ``health`` enables the training-health monitors (default: the
        ``AUTODIST_HEALTH`` flag) on the synchronous runner: the jitted step
        additionally emits the fused numerics bundle ``train()``'s monitors
        consume at log boundaries. See docs/usage/observability.md
        "Training health monitors".

        ``tune`` runs the plan autotuner before the session is built
        (default: ``AutoDist(strategy_builder="autotune")`` or the
        ``AUTODIST_TUNE`` flag): the predict-prune-probe search
        (:func:`autodist_tpu.strategy.autotune.autotune`) picks the builder
        plus ``unroll``/``zero``/``accumulation_steps`` and this session
        applies them — explicit ``zero``/``accumulation_steps`` arguments
        win over the tuned values. The winner lands in the
        ``AUTODIST_PLAN_CACHE`` file, so a warm relaunch of the same job
        applies the tuned plan with zero search cost; the applied plan is
        recorded in the profile/flight-recorder manifests and on
        ``runner.tuned_plan`` (``train()`` adopts its ``unroll`` when none
        is passed). See docs/usage/performance.md "Plan autotuning".
        """
        self._maybe_autotune(tune, loss_fn, params, optimizer, example_batch,
                             sparse_names, has_aux)
        plan_knobs = self._tuned_plan
        if plan_knobs is not None:
            if accumulation_steps == 1:
                accumulation_steps = plan_knobs.accumulation_steps
            if zero is None and plan_knobs.zero:
                zero = plan_knobs.zero
        model_spec = self._model_spec_for(loss_fn, params, example_batch, sparse_names)
        # Builders that model memory (AutoStrategy) get the session's optimizer
        # so regime decisions use exact state bytes, not an Adam-class guess.
        observe = getattr(self._strategy_builder, "observe_optimizer", None)
        if observe is not None:
            observe(optimizer)
        strategy = self.build_strategy(model_spec)
        # Compile BEFORE multi-node setup: the plan's is_async is the single
        # source of truth for which communication plane _setup wires (pure proto
        # work — touches no backend, so it is safe pre-jax.distributed).
        compiled = self._compile(model_spec)
        from autodist_tpu.parallel.plan import ShardingPlan
        plan = ShardingPlan.from_strategy(compiled, model_spec)
        if plan.is_async and accumulation_steps > 1:
            # Before _setup: failing after Cluster.start() would leave launched
            # worker processes behind on a call that returns nothing.
            raise ValueError(
                "accumulation_steps > 1 is a synchronous-runner feature; the "
                "async/bounded-stale regime steps micro-batches as ordinary steps")
        self._setup(strategy, async_mode=plan.is_async)
        if plan.is_async:
            from autodist_tpu.parallel.staleness import AsyncPSRunner
            # Multi-node async: one worker slot per launched process (each steps
            # through the PS transport), else the documented single-slot default.
            if num_workers:
                workers = num_workers
            elif self._cluster is not None:
                workers = self._cluster.num_processes
            else:
                workers = 1
            runner = AsyncPSRunner(compiled, model_spec, loss_fn, optimizer,
                                   has_aux=has_aux, num_workers=workers, plan=plan,
                                   ps_address=getattr(self, "_ps_address", None)
                                   or (const.ENV.AUTODIST_PS_ADDR.val or None),
                                   zero=zero)
            runner._ps_listen_sock = getattr(self, "_ps_listen_sock", None)
            runner.tuned_plan = self._tuned_plan
            self._session = runner  # _teardown closes its transport endpoints
            return runner
        runner = DistributedRunner(compiled, model_spec, loss_fn, optimizer,
                                   has_aux=has_aux, plan=plan,
                                   accumulation_steps=accumulation_steps,
                                   batch_size=batch_size, zero=zero,
                                   health=health)
        runner.tuned_plan = self._tuned_plan
        return runner

    def _maybe_autotune(self, tune: Optional[bool], loss_fn, params, optimizer,
                        example_batch, sparse_names, has_aux):
        """Run the plan autotuner once per instance (before the first
        strategy build) and install the winning builder; later sessions on
        this instance reuse the already-built strategy. No-ops off the
        chief, without an example batch, or on multi-node specs (the search
        measures locally — same contract as ``tune_strategy``)."""
        if tune is None:
            tune = self._autotune or const.ENV.AUTODIST_TUNE.val
        if not tune or self._tuned_plan is not None:
            return
        if self._strategy is not None or self._compiled is not None:
            logging.warning("AutoDist: tune requested after a strategy was "
                            "already built; keeping the existing strategy")
            return
        if not self.is_chief:
            return   # workers load the chief's strategy id as usual
        import jax
        if jax.process_count() > 1:
            # A multi-process SPMD program must compile IDENTICAL step
            # programs everywhere, but only the builder travels via the
            # strategy id — a chief-tuned zero/unroll knob would diverge
            # from the workers' defaults and wedge the collectives. Tune a
            # single-process launch and ship the winning knobs explicitly.
            logging.warning(
                "AutoDist: tune=True in a multi-process SPMD program — the "
                "tuned execution knobs (zero/unroll/accumulation) cannot "
                "ship to the other processes, so the search is skipped; "
                "tune single-process and pass the winning knobs explicitly")
            return
        if example_batch is None:
            logging.warning("AutoDist: tune=True needs an example_batch to "
                            "probe candidate plans; skipping the search")
            return
        if self._resource_spec.num_nodes > 1:
            logging.warning(
                "AutoDist: tune=True on a multi-node spec — the autotuner "
                "measures on local devices only and would mis-rank "
                "cross-node plans; skipping the search (tune on a "
                "single-node spec and ship the winning builder)")
            return
        from autodist_tpu.strategy.autotune import autotune as _search
        from autodist_tpu.telemetry import profiling as _profiling
        try:
            plan = _search(loss_fn, params, optimizer, example_batch,
                           resource_spec=self._resource_spec,
                           sparse_names=sparse_names, has_aux=has_aux)
        except Exception as e:  # noqa: BLE001 — a failed search must degrade
            # Same contract as the other skip paths above: tuning is an
            # optimization, so a backend with no cost analysis (or every
            # probe failing) falls back to the default builder with a
            # warning instead of killing the launch.
            logging.warning("AutoDist: plan autotune failed (%s: %s); "
                            "keeping the default strategy builder",
                            type(e).__name__, e)
            return
        self._tuned_plan = plan
        self._strategy_builder = plan.make_builder()
        # The applied plan travels with every diagnostic artifact: profile
        # JSONs and flight-recorder manifests name which plan a run was
        # executing (cache key + knobs + predicted vs measured).
        _profiling.set_applied_plan(dict(plan.to_dict(), name=plan.name))
        logging.info("AutoDist: applying tuned plan %s (%s)", plan.name,
                     "cache hit" if plan.from_cache else
                     f"searched in {plan.search_s:.2f}s")

    def _model_spec_for(self, loss_fn, params, example_batch, sparse_names) -> ModelSpec:
        if sparse_names is not None:
            return ModelSpec(params, sparse_names=sparse_names)
        if example_batch is not None:
            return ModelSpec.from_loss_fn(loss_fn, params, example_batch)
        return ModelSpec(params)

    # ----------------------------------------------------------------- function
    def function(self, loss_fn: Callable, params: Any, optimizer,
                 example_batch: Any = None, sparse_names: Optional[Sequence[str]] = None,
                 has_aux: bool = False, accumulation_steps: int = 1,
                 batch_size: Optional[int] = None,
                 zero: Optional[Any] = None,
                 health: Optional[bool] = None,
                 tune: Optional[bool] = None) -> Callable:
        """TF2-style stepping: returns ``step(batch) -> loss`` carrying state
        internally (reference autodist.py:252-289 cached a built runner the same
        way: first call builds, later calls reuse).

        Async strategies: the ``step`` closure is one worker's loop (the reference
        ran one such loop per process); the worker pool is sized by the cluster —
        one slot per launched process, or a single slot for single-node runs (an
        in-process phantom worker that never steps would deadlock the gate).

        All of the call is the set-up phase ``setup.function_s`` (model spec,
        strategy, plan, mesh, the first state placed)."""
        with telemetry.phase("setup.function_s"):
            runner = self.create_distributed_session(
                loss_fn, params, optimizer, example_batch, sparse_names,
                has_aux, accumulation_steps=accumulation_steps,
                batch_size=batch_size, zero=zero, health=health, tune=tune)
            state = runner.init(params)

        def step(batch, fetches=None):
            nonlocal state
            if fetches is None:
                state, fetched = runner.run(state, batch)
            else:
                # Synchronous runners only; the async regime has no in-step
                # fetch point (its TypeError names the unsupported keyword).
                state, fetched = runner.run(state, batch, fetches=fetches)
            return fetched

        step.runner = runner
        step.get_state = lambda: state
        if not runner.plan.is_async:
            # Sync runner only: the async regime's worker-side local state is a
            # pass-through template (the chief's PS state is authoritative), so
            # an inherited evaluate would silently score untrained params.
            step.evaluate = lambda batch, fn=None: runner.evaluate(state, batch, fn)
        return step
