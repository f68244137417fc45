"""Training loop with automatic checkpoint/resume.

The reference left the loop to user scripts (session.run loops, Keras fit) and
proved resumability with its NFS saver case — chief-gated saves on a shared
filesystem (``tests/integration/cases/c10.py:1-12``). This is that contract as
an API: periodic chief-gated saves under original names, automatic resume from
the latest checkpoint, throughput metering, and a final save — so a preempted
run restarted with the same command continues where it stopped.
"""

from typing import Any, Callable, Iterable, Optional, Union

import jax
import numpy as np

from autodist_tpu import const, telemetry
from autodist_tpu.checkpoint.saver import Saver
from autodist_tpu.data import prefetch as _prefetch
from autodist_tpu.parallel import recovery as _recovery
from autodist_tpu.runner import MicroBatched, TrainState
from autodist_tpu.testing import faults as _faults
from autodist_tpu.telemetry import health as _health
from autodist_tpu.telemetry import history as _history
from autodist_tpu.telemetry import memplane as _memplane
from autodist_tpu.telemetry import openmetrics as _openmetrics
from autodist_tpu.telemetry import profiling as _profiling
from autodist_tpu.utils import logging
from autodist_tpu.utils.metrics import ThroughputMeter

PyTree = Any


def _observe_health(monitor, runner, step: int, losses,
                    state: TrainState):
    """Feed the health monitor at a log boundary (where the loss readback
    already synced) and apply the policy: ``losses`` is the period's
    per-step loss values (host-side), the bundle is the runner's latest
    device readback. Raises :class:`telemetry.HealthHalt` with the LIVE
    state attached under ``AUTODIST_HEALTH_ACTION=halt``, or
    :class:`telemetry.HealthRecover` under ``recover`` — ``train()``'s
    retry wrapper catches the latter, rolls back to the newest
    last-known-good snapshot, and resumes."""
    bundle = getattr(runner, "last_health", None)
    if bundle is not None:
        bundle = jax.device_get(bundle)
    anomalies = monitor.observe(step, losses, bundle)
    if anomalies and monitor.should_recover:
        raise _health.HealthRecover(step, state, anomalies)
    if anomalies and monitor.should_halt:
        raise _health.HealthHalt(step, state, anomalies)


def _make_meter(first_batch: PyTree, batch_size: Optional[int],
                log_every: int) -> ThroughputMeter:
    """Meter sized lazily from the first batch: the largest leading dim fixes
    the example count per step. A batch that already went through
    ``shard_batch`` under gradient accumulation carries ``MicroBatched``
    leaves laid out ``[k, B/k, ...]`` — fold those back to ``B``."""
    n = batch_size
    if n is None:
        dims = []
        for leaf in jax.tree_util.tree_leaves(
                first_batch, is_leaf=lambda x: isinstance(x, MicroBatched)):
            if isinstance(leaf, MicroBatched):
                v = leaf.value
                if getattr(v, "ndim", 0) >= 2:
                    dims.append(v.shape[0] * v.shape[1])
            elif getattr(leaf, "ndim", 0) >= 1:
                dims.append(leaf.shape[0])
        n = max(dims, default=1)
    return ThroughputMeter(batch_size=n, log_every=log_every, log=False)


def train(runner, params: PyTree,
          batches: Union[Callable[[int], PyTree], Iterable[PyTree]],
          steps: int,
          checkpoint_dir: Optional[str] = None,
          checkpoint_name: str = "model",
          save_every: int = 1000,
          max_to_keep: int = 5,
          log_every: int = 100,
          batch_size: Optional[int] = None,
          is_chief: Optional[bool] = None,
          resume: bool = True,
          async_save: bool = False,
          on_metrics: Optional[Callable[[int, float, float], None]] = None,
          eval_every: int = 0,
          eval_batch: Any = None,
          eval_fn: Optional[Callable] = None,
          on_eval: Optional[Callable[[int, Any], None]] = None,
          unroll: Optional[int] = None,
          prefetch_depth: Optional[int] = None,
          health_monitor: Optional["_health.HealthMonitor"] = None) -> TrainState:
    """Run ``steps`` global steps, checkpointing and resuming automatically.

    ``batches``: either ``fn(step_index) -> batch`` or an iterable of batches
    (exhaustion ends the run early). In a multi-process SPMD program
    (``jax.process_count() > 1``) saves are COLLECTIVE: every process calls
    :meth:`Saver.save` at the same step, writes the state shards it owns, and
    only the chief publishes the manifest + rotation — the c10
    shared-filesystem protocol against cross-process-sharded state. With one
    process (or async-PS worker roles), saves stay chief-only.
    ``async_save=True`` makes PERIODIC saves double-buffered (device snapshot
    synchronous, file IO behind the step loop — :meth:`Saver.save`); the
    final save is always synchronous, so the returned state is durably on
    disk. ``on_metrics(step, loss, rate)`` fires every
    ``log_every`` steps. With ``eval_every`` and ``eval_batch``, the runner's
    forward-only :meth:`evaluate` runs every ``eval_every`` steps on the
    current params (``eval_fn`` defaults to the loss) and ``on_eval(step,
    value)`` receives the result. Returns the final :class:`TrainState`.

    ``unroll=None`` (the default) adopts the runner's tuned plan when one is
    attached (``create_distributed_session(tune=True)`` sets
    ``runner.tuned_plan``; its ``unroll`` is the autotuner's measured
    winner) and otherwise behaves as ``unroll=1``; pass an explicit value
    to override the tuned knob.

    ``unroll=K`` (K > 1) makes one dispatch a block of steps: K consecutive
    batches are stacked into one pre-sharded block and run as ONE compiled
    K-step program (:meth:`DistributedRunner.run_many` — bit-identical to K
    per-step calls), while the host gathers and pre-shards the next block
    behind the running one. Checkpoint and eval cadence points force block
    boundaries, so saves/evals fire at exactly the steps ``unroll=1`` fires
    them at and resume semantics are unchanged (step i still consumes
    batch i); only logging moves to block granularity (the first block is the
    meter's warmup, periods close at the first block end with ``log_every``
    post-warmup steps, and ``on_metrics`` receives the block's last loss).
    Runners without fused support (async-PS, remote workers) fall back to
    one step a dispatch with a warning.

    ``prefetch_depth`` arms the async input pipeline
    (:mod:`autodist_tpu.data.prefetch`): a background producer pulls up to
    ``prefetch_depth`` batches (blocks, under ``unroll=K``) ahead of the
    step and applies the feed remapping (``shard_batch``/``shard_block``)
    there, so host loading and host->HBM transfer overlap the running
    step; ``train.data_wait`` then measures only the residual queue wait,
    while the ``data.producer_wait`` counter keeps naming a slow loader.
    ``None`` adopts the tuned plan's ``prefetch_depth`` when one is
    attached and nonzero, else the ``AUTODIST_PREFETCH_DEPTH`` flag
    (default 0 = the synchronous feed, batches pulled exactly at their
    step). Prefetching calls the batch source up to ``prefetch_depth``
    items ahead (an iterable may be advanced past the last consumed step
    at shutdown); exceptions from the source re-raise at the consuming
    step, and exhaustion ends the run exactly like the synchronous path.

    ``health_monitor`` overrides the ``AUTODIST_HEALTH`` default (a
    :class:`telemetry.HealthMonitor`, or the flag builds one): the monitor
    consumes each log period's per-step losses plus the runner's fused
    on-device numerics bundle at the SAME boundary where the loss readback
    already syncs — zero extra dispatches, zero extra syncs. Anomalies
    (NaN/Inf, loss spikes) become ``health.anomaly`` events and follow the
    ``AUTODIST_HEALTH_ACTION`` policy; ``halt`` raises
    :class:`telemetry.HealthHalt` carrying the live state. Monitoring needs
    ``log_every > 0`` (boundaries are where readbacks happen).

    ``AUTODIST_HEALTH_ACTION=recover`` (and its alert-engine twin
    ``AUTODIST_ALERT_ACTION=recover``) turns detection into self-healing:
    the loop keeps a bounded ring of last-known-good states captured at
    health-clean log boundaries, and an anomaly ROLLS BACK to the newest
    good one and resumes — replaying the rolled-back steps exactly when
    ``batches`` is a callable (an iterable source continues on its next
    unconsumed items instead). At most ``AUTODIST_RECOVER_MAX`` rollback
    attempts; exhaustion (or an anomaly before any healthy boundary)
    escalates to the existing :class:`telemetry.HealthHalt` /
    :class:`telemetry.AlertHalt`. See ``docs/usage/resilience.md``.
    """
    # Set-up's last phase: from here to the loop, whose first act is its first
    # pull from the batch source (the second runner.init, the saver, the
    # monitors).
    with telemetry.phase("setup.train_enter_s"):
        if unroll is None:
            tuned = getattr(runner, "tuned_plan", None)
            unroll = int(getattr(tuned, "unroll", 1) or 1)
            if unroll > 1:
                logging.info("train: adopting tuned plan unroll=%d (%s; pass "
                             "unroll= explicitly to override)", unroll,
                             getattr(tuned, "name", "tuned plan"))
        if unroll < 1:
            raise ValueError("unroll must be >= 1")
        if prefetch_depth is None:
            tuned = getattr(runner, "tuned_plan", None)
            tuned_depth = int(getattr(tuned, "prefetch_depth", 0) or 0)
            if tuned_depth > 0:
                logging.info("train: adopting tuned plan prefetch_depth=%d "
                             "(pass prefetch_depth= explicitly to override)",
                             tuned_depth)
                prefetch_depth = tuned_depth
            else:
                prefetch_depth = _prefetch.default_prefetch_depth()
        prefetch_depth = max(0, int(prefetch_depth))
        if eval_every and eval_batch is None:
            raise ValueError("eval_every needs an eval_batch")
        if is_chief is None:
            is_chief = const.is_chief_process()
        # Scrape endpoint: AUTODIST_METRICS_PORT attaches /metrics + /healthz to
        # the trainer process too (PSServer/InferenceServer processes attach in
        # their constructors; the process-global exporter binds once either way).
        _openmetrics.maybe_serve()
        # Sharded (multi-process SPMD) saves are collective: every process must
        # participate — each writes the shards it owns; the Saver itself gates
        # manifest/rotation to process 0. Chief-only gating remains for
        # single-process programs (incl. async-PS roles, where each process is
        # its own jax program).
        save_participant = is_chief or jax.process_count() > 1
        saver = Saver(max_to_keep=max_to_keep) if checkpoint_dir else None
        prefix_base = f"{checkpoint_dir}/{checkpoint_name}" if checkpoint_dir else None

        state = None
        if saver is not None and resume:
            latest = Saver.latest_checkpoint(checkpoint_dir, name=checkpoint_name)
            if latest is not None:
                state = saver.restore(latest, runner=runner)
                logging.info("train: resumed from %s at step %d", latest,
                             int(state.step))
        if state is None:
            state = runner.init(params)

        next_batch = batches if callable(batches) else None
        batch_iter = iter(batches) if next_batch is None else None

        start = int(state.step)
        if batch_iter is not None and start > 0:
            # Resume with an iterable: fast-forward so step i still consumes batch i —
            # replaying from item 0 would retrain on already-seen data and break the
            # identical-resume contract.
            logging.info("train: fast-forwarding batch iterator by %d consumed steps",
                         start)
            for _ in range(start):
                try:
                    next(batch_iter)
                except StopIteration:
                    return state
        monitor = health_monitor if health_monitor is not None \
            else _health.HealthMonitor.from_env()
        if monitor is not None and not log_every:
            logging.warning("train: health monitors need log_every > 0 (the "
                            "bundle readback rides log boundaries); disabling "
                            "them for this run")
            monitor = None
        use_blocks = (unroll > 1 and getattr(runner, "supports_run_many", False)
                      and not getattr(runner, "_is_remote_worker", False))
        if unroll > 1 and not use_blocks:
            logging.warning(
                "train: unroll=%d requested but %s has no fused multi-step path "
                "(async/remote regime); falling back to per-step dispatch",
                unroll, type(runner).__name__)

        def _finish(final_state: TrainState) -> TrainState:
            # End-of-run attribution flush (the health monitors' PR 8 contract,
            # re-established here): a final partial period — steps not a
            # multiple of log_every, or a run shorter than one period — still
            # reaches the series; require_steps drops a dispatch-less tail.
            # BEFORE the final save: a multi-second synchronous checkpoint
            # would otherwise land in the tail period's compute residual and
            # inflate the profile's period-weighted step_s.
            if _profiling.active():
                _profiling.observe_period(int(final_state.step),
                                          require_steps=True)
            # End-of-run history flush (forced past the throttle): a run shorter
            # than one min_interval_s window still leaves at least one sample —
            # and its final alert tick — in the ring/shards. AFTER the closing
            # observe_period so the sample carries the tail period's gauges;
            # BEFORE the final save so a halt-action alert stops us with the
            # state unsaved-but-LIVE on the exception, exactly like HealthHalt.
            try:
                _history.maybe_sample(int(final_state.step), reason="final",
                                      force=True)
            except telemetry.AlertHalt as e:
                e.state = final_state
                raise
            # Final save stays synchronous: train() returning means the state is
            # durably on disk (save() joins any in-flight periodic write first).
            if saver is not None and save_participant and int(final_state.step) > start:
                with telemetry.span("train.checkpoint", final=True):
                    saver.save(final_state, prefix_base, runner=runner)
            if saver is not None:
                saver.wait()
            # Per-run profile store: with the attribution plane armed and
            # AUTODIST_PROFILE_DIR set, the run's profile JSON (program costs +
            # attribution series) lands on disk for adprof/costmodel.
            _profiling.maybe_write_profile()
            return final_state

        # Recover-and-resume policy (parallel/recovery.py): under
        # AUTODIST_HEALTH_ACTION=recover (or the alert-engine twin) the loop
        # pushes the state into a bounded last-known-good ring at every HEALTHY
        # log boundary, and an anomaly rolls back to the newest good snapshot
        # and re-enters the loop — bounded by AUTODIST_RECOVER_MAX attempts
        # before escalating to the existing halt.
        recover_armed = (
            (monitor is not None and monitor.config.action == "recover")
            or str(const.ENV.AUTODIST_ALERT_ACTION.val) == "recover")
        ring = None
        if recover_armed:
            # Ring entries must OWN their buffers: the sync runner's step
            # DONATES its input state, so a bare reference would be deleted by
            # the dispatch right after the push. One fused on-device copy per
            # healthy boundary (sharding-preserving; recover is opt-in and log
            # boundaries are sparse — the copy is the price of a rollback
            # target that survives donation).
            import jax.numpy as jnp
            ring = _recovery.SnapshotRing(copy_fn=jax.jit(
                lambda s: jax.tree_util.tree_map(jnp.copy, s)))
        if ring is not None and batch_iter is not None:
            logging.warning(
                "train: recover action with an ITERABLE batch source — a "
                "rollback cannot replay consumed batches, so the resumed loop "
                "continues on the next unconsumed ones (pass a callable "
                "batches(step) source for exact replay)")

        def _run_attempt(attempt_state: TrainState) -> TrainState:
            """One pass of the loop from ``attempt_state``'s own step — the
            source and its feed are (re)built per attempt so a rollback's replay
            pulls the rolled-back step range, not the crashed attempt's
            readahead."""
            source = _BatchSource(next_batch, batch_iter, int(attempt_state.step),
                                  steps)
            if use_blocks:
                periods = (save_every if saver is not None else 0, eval_every)
                pull = lambda: source.pull_block(unroll, periods)  # noqa: E731
                shard = runner.shard_block
            else:
                pull = source.pull
                # Async/remote regimes prefetch host batches only.
                shard = getattr(runner, "shard_batch", None)
                if not callable(shard) or getattr(runner, "_is_remote_worker",
                                                  False):
                    shard = None
            # Async input pipeline: with prefetch_depth > 0 a background producer
            # pulls host batches (or cadence-clipped blocks of them) AND applies
            # the feed remapping (shard_batch, or shard_block = stacking + async
            # device_put) up to `depth` items ahead, so the loop's
            # train.data_wait span measures only the residual queue wait. The
            # producer books data.producer_wait/queue_depth, keeping a slow
            # loader visible.
            producer = _prefetch.PrefetchProducer(
                pull, shard, depth=prefetch_depth,
                workers=_prefetch.default_prefetch_workers(),
                name="train-feed") if prefetch_depth > 0 else None
            if telemetry.enabled():
                # The HBM account (telemetry/memplane.py) notes the
                # allocator's lifetime peak as the loop finds it.
                _memplane.open_hbm_account()
            try:
                return _loop(
                    runner, attempt_state, source, producer, pull, use_blocks,
                    steps, saver, prefix_base, save_participant, save_every,
                    async_save, log_every, batch_size, on_metrics, eval_every,
                    eval_batch, eval_fn, on_eval, monitor, ring)
            finally:
                if producer is not None:
                    producer.close()

    # Set-up ends here: the loop's first act is its first pull from the batch
    # source. The ledger freezes its sum and logs its one line.
    telemetry.phases.mark_setup_end()
    attempt = 0
    last_fail_step = None
    while True:
        try:
            state = _run_attempt(state)
            break
        except (_health.HealthRecover, telemetry.AlertRecover) as e:
            # AUTODIST_RECOVER_MAX bounds attempts PER INCIDENT, not per
            # run: an anomaly at a LATER step than the last one means the
            # earlier incident was overcome (training progressed past it),
            # so the budget resets — three transient spikes hours apart
            # must not spend a lifetime cap and turn the fourth into a
            # halt. A repeat at the same (or an unknown) step is the same
            # incident and keeps counting toward escalation.
            fail_step = getattr(e, "step", None)
            if fail_step is not None and last_fail_step is not None \
                    and fail_step > last_fail_step:
                attempt = 0
            if fail_step is not None:
                last_fail_step = fail_step
            attempt += 1
            # Returns the newest good state (re-seeding an async runner's
            # service), or escalates to HealthHalt/AlertHalt when the ring
            # is empty or AUTODIST_RECOVER_MAX is spent.
            state = _recovery.rollback(e, ring, attempt,
                                       _recovery.recover_max(),
                                       runner=runner)
    return _finish(state)


class _BatchSource:
    """Host batches in step order from ``fn(step) -> batch`` or an iterator:
    the ONE place ``train()`` pulls a batch, called inline by the loop or by
    the prefetch producer's thread. Pulls stop at ``steps`` — a callable
    source is never invoked past the last step it could train (readahead
    must not call user code out of the run's contract) — and at the
    iterator's exhaustion, which is reported once."""

    def __init__(self, next_batch, batch_iter, start: int, steps: int):
        self.first = None   # the first batch pulled: sizes the meter
        self._next_batch = next_batch
        self._batch_iter = batch_iter
        self._cursor = start
        self._steps = steps
        self._exhausted = False

    def pull(self):
        """The batch of the next step; ``StopIteration`` when the run is
        over."""
        i = self._cursor
        if self._exhausted or i >= self._steps:
            raise StopIteration
        if self._next_batch is not None:
            batch = self._next_batch(i)
        else:
            try:
                batch = next(self._batch_iter)
            except StopIteration:
                self._exhausted = True
                logging.info("train: batch iterator exhausted at step %d", i)
                raise
        if _faults.armed() and _faults.should_fire("nan_grads", step=i):
            # Chaos harness (testing/faults.py): NaN-fill the batch's float
            # leaves so the REAL compiled step produces real NaN gradients —
            # the recover-action tests and bench drive genuine anomalies,
            # not mocks. Keyed by the step the batch is FOR, so it fires in
            # a block as it does alone; a producer's readahead can consume a
            # firing for a step a rollback then never reaches. Un-armed
            # cost: one module-global read per step.
            logging.warning("faults: injecting NaN batch at step %d", i)
            batch = _faults.corrupt_batch(batch)
        if self.first is None:
            self.first = batch
        self._cursor = i + 1
        return batch

    def pull_block(self, unroll: int, periods) -> list:
        """Up to ``unroll`` batches from the next step on, clipped so that a
        block ENDS at every multiple of a nonzero ``periods`` entry (the
        save and eval cadences; ``pull`` stops it at ``steps``) — which keeps
        checkpoint/eval/resume semantics those of one step a dispatch. A
        source that exhausts mid-block still emits the partial block: those
        steps were consumed and must train."""
        i = self._cursor
        end = min([i + unroll] + [(i // p + 1) * p for p in periods if p])
        blk = []
        for _ in range(end - i):
            try:
                blk.append(self.pull())
            except StopIteration:
                break
        if not blk:
            raise StopIteration
        return blk


def _flat_losses(pending) -> np.ndarray:
    """One host array of per-step losses from a period's dispatches (device
    scalars, or ``[K]`` stacks of a block), read back together."""
    return np.concatenate([np.asarray(l).reshape(-1)
                           for l in jax.device_get(pending)])


def _loop(runner, state: TrainState, source: _BatchSource, producer, pull,
          use_blocks: bool, steps: int, saver, prefix_base, save_participant,
          save_every: int, async_save: bool, log_every: int,
          batch_size: Optional[int], on_metrics, eval_every: int, eval_batch,
          eval_fn, on_eval, monitor, ring) -> TrainState:
    """The training loop: pull, dispatch, meter, and at a closed log period
    the boundary; then the eval / save cadence. What ONE dispatch is, is the
    only thing ``unroll`` decides.

    ``unroll == 1``: ``runner.run(state, batch)`` advances one step and
    fetches a scalar loss. ``unroll > 1`` (``use_blocks``): up to ``unroll``
    consecutive batches, clipped at cadence points, run as one compiled
    K-step scan (:meth:`DistributedRunner.run_many`) fetching a ``[K]`` loss
    stack; dispatch is asynchronous, so the host gathers and pre-shards the
    next block while the device executes this one, and losses are read back
    only when a ``log_every`` period closes at a block end.

    With ``producer`` (``train(prefetch_depth>0)``) the items arrive
    pre-sharded from its thread instead of from ``pull`` here:
    ``train.data_wait`` then measures only the residual queue wait, and the
    producer's ``data.*`` telemetry carries the loader cost. ``ring`` (a
    :class:`recovery.SnapshotRing`) receives the state at every boundary
    that closes healthy — the recover action's rollback targets."""
    next_item = pull if producer is None else producer.__next__
    meter = None
    step_i = int(state.step)
    # Health monitoring: every dispatch's device losses accumulate here (tiny
    # device arrays, no sync) and are read back together at the log boundary
    # — so the spike detector sees EVERY step's loss while the loop still
    # syncs only once per period.
    pending_losses = []
    while True:
        try:
            with telemetry.span("train.data_wait"):
                item = next_item()
        except StopIteration:
            break
        if use_blocks:
            if producer is None:
                # A train.dispatch SIBLING, not a child: the attribution
                # plane adds these spans to the host phase on that footing.
                with telemetry.span("runner.shard_block"):
                    item = runner.shard_block(item)
            n = item.length
            with telemetry.span("train.dispatch", steps=n):
                state, fetched = runner.run_many(state, item)
        else:
            n = 1
            with telemetry.span("train.dispatch"):
                state, fetched = runner.run(state, item)
        losses = fetched[0] if isinstance(fetched, tuple) else fetched
        step_i += n
        if monitor is not None:
            pending_losses.append(losses)
        # The producer's fill (0 without one: items are pulled exactly at
        # their dispatch). 0 under prefetch means the loader is not keeping
        # up — the host failed to stay ahead of the device.
        queue_depth = producer.queue_depth() if producer is not None else 0
        if telemetry.enabled():
            telemetry.gauge("train.dispatch_queue_depth").set(queue_depth)
        if meter is None and log_every:
            meter = _make_meter(source.first, batch_size, log_every)
        if meter is not None:
            # The meter syncs (device->host read of the losses) only where a
            # period closes — not per dispatch — and its first dispatch is
            # warmup (it carries the compile), so boundaries land at
            # 1 + k*log_every local steps for single steps, and at the first
            # block end with >= log_every post-warmup steps for blocks.
            rate = meter.step_many(n, sync=losses)
            if rate is not None:
                _log_boundary(runner, state, step_i, losses, rate, meter,
                              queue_depth, monitor, pending_losses, ring,
                              on_metrics)
        if (eval_every and step_i % eval_every == 0
                and not getattr(runner, "_is_remote_worker", False)):
            # Async remote workers skip: their local state is a compile-shapes
            # template and AsyncPSRunner.evaluate raises there by design. Sync
            # SPMD processes all evaluate together (the compiled eval is a
            # collective program).
            with telemetry.span("train.eval"):
                val = runner.evaluate(state, eval_batch, eval_fn)
            try:
                logging.info("train: step %d eval %.6f", step_i, float(val))
            except (TypeError, ValueError):
                logging.info("train: step %d eval (pytree)", step_i)
            if on_eval is not None:
                on_eval(step_i, val)
        if (saver is not None and save_participant and save_every
                and step_i % save_every == 0 and step_i < steps):
            with telemetry.span("train.checkpoint"):
                saver.save(state, prefix_base, runner=runner,
                           async_write=async_save)

    if monitor is not None and pending_losses:
        # End-of-run flush: a NaN in the final partial period (steps not a
        # multiple of log_every) must still anomaly/snapshot/halt — the
        # monitor's contract is EVERY step observed, not every full period.
        _observe_health(monitor, runner, step_i, _flat_losses(pending_losses),
                        state)
    if meter is not None:
        meter.finish()   # freeze the run clock: average stays the TRAIN rate
    return state


def _log_boundary(runner, state: TrainState, step: int, losses, rate: float,
                  meter: ThroughputMeter, queue_depth: int, monitor,
                  pending_losses: list, ring, on_metrics):
    """What a closed log period pays, from the meter's return to the next
    feed: what the device waits for. The span's two children are what only a
    traced run pays (planes) and the caller's callback; the rest is what
    every run pays. The ORDER is the contract — each stage reads what the
    one before it booked."""
    with telemetry.span("train.boundary"):
        # The period's attribution closes HERE — after the meter's boundary
        # sync recorded its readback span, before the snapshot below is
        # emitted — so the train.attr.*/mfu gauges it books describe exactly
        # this period.
        attr = _profiling.observe_period(step) \
            if _profiling.active() else None
        # The meter's sync already read `losses` back: this copies nothing.
        last = float(np.asarray(losses).reshape(-1)[-1])
        # Async-PS runs append their transport accounting (zero-copy wire
        # counters) so per-period logs show parameter/gradient traffic next
        # to throughput. `q` is the input queue depth, `rb` the seconds this
        # period spent blocked on device->host readback — together they say
        # whether a slow period was compute, readback, or host-side stall,
        # from the log line alone.
        stats = getattr(runner, "wire_stats", None)
        stats = stats() if callable(stats) else None
        logging.info("train: step %d loss %.4f %.1f examples/s "
                     "| q %d rb %.3fs%s%s",
                     step, last, rate, queue_depth, meter.last_readback_s,
                     f" | {stats.format_line()}" if stats else "",
                     _profiling.format_attr_line(attr))
        # The period's throughput as a gauge: the fleet console
        # (tools/adfleet.py) compares steps/s across processes off the
        # status opcode, so the rate must live in the registry, not just the
        # log line. One gauge set per log boundary.
        telemetry.gauge("train.steps_per_s").set(
            round(rate / meter.batch_size, 4))
        if telemetry.enabled():
            with telemetry.span("train.boundary.planes"):
                # Memory gauges first so the snapshot emitted below carries
                # this boundary's live-buffer/HBM readings (and the opt-state
                # footprint ZeRO sharding divides). One walk of the state
                # re-points the census tags at THIS boundary's arrays — the
                # step donates its inputs, so last boundary's claims are dead
                # weakrefs by now — and counts it for the HBM account.
                telemetry.sample_device_memory(state=state)
                telemetry.emit_metrics(global_step=step)
        if monitor is not None:
            _observe_health(monitor, runner, step,
                            _flat_losses(pending_losses), state)
            pending_losses.clear()
        # Metric-history sample LAST of the planes, so the sample (and the
        # alert rules it evaluates) sees this period's attr/mfu/health/
        # throughput gauges. An AlertHalt under AUTODIST_ALERT_ACTION=halt
        # propagates from here — the train loop is the sampler a halt can
        # actually stop — with the LIVE TrainState attached (the HealthHalt
        # contract: a halt leaves the state checkpointable, not discarded).
        try:
            _history.maybe_sample(step)
        except telemetry.AlertHalt as e:
            e.state = state
            raise
        # The boundary closed HEALTHY (no health anomaly raised, no alert
        # fired past this point): this state is a valid rollback target.
        # push() DEEP-COPIES on device via the ring's copy_fn — the step
        # donates its input buffers, so a bare reference would be deleted by
        # the next dispatch.
        if ring is not None:
            ring.push(step, state)
            if telemetry.enabled():
                # Ring census: the deep-copied snapshot states are pinned
                # device memory nothing else accounts for.
                _memplane.tag("snapshots", ring.states())
        if on_metrics is not None:
            with telemetry.span("train.boundary.on_metrics"):
                on_metrics(step, last, rate)
