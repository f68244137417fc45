"""Input-data plane: the unified async sharded prefetch pipeline.

The pipeline (``data/prefetch.py``) is a pure performance transform over the
synchronous feed — same batches, same order, same math — so the contracts
asserted here are exact: bit-identical training results (per-step AND
``unroll=K`` blocks), bounded queue depth, producer exceptions re-raised at
the consumer, clean close with a blocked producer, clean exhaustion (no
PEP 479 ``RuntimeError``), per-host shard disjointness keyed off the
runner's feed layout, producer-wait telemetry, typed flags, and the
autotuner enumerating + pricing the ``prefetch_depth`` knob.

Pure in-process (no subprocess): named to sort in-window, right after
test_data_loader.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import AutoDist, const, telemetry, train
from autodist_tpu.data import DataLoader, device_prefetch
from autodist_tpu.data import prefetch as pf
from autodist_tpu.runner import BatchBlock
from autodist_tpu.strategy import AllReduce

BATCH = 32


def _loss(p, b):
    return jnp.mean((b["y"] - (b["x"] @ p["w"] + p["b"])) ** 2)


def _params():
    rng = np.random.RandomState(7)
    return {"w": rng.randn(4, 1).astype(np.float32),
            "b": np.zeros((1,), np.float32)}


def _batch_fn(i):
    rng = np.random.RandomState(100 + i)
    return {"x": rng.randn(BATCH, 4).astype(np.float32),
            "y": rng.randn(BATCH, 1).astype(np.float32)}


def _session(accum=1):
    ad = AutoDist(strategy_builder=AllReduce())
    runner = ad.create_distributed_session(
        _loss, _params(), optax.adam(1e-2), example_batch=_batch_fn(0),
        accumulation_steps=accum)
    return runner, runner.init(_params())


def _assert_trees_equal(a, b):
    a, b = jax.device_get(a), jax.device_get(b)
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# --------------------------------------------------------- queue core

def test_bounded_queue_basics_and_close_semantics():
    q = pf.BoundedQueue(2)
    assert q.try_put(1) and q.try_put(2)
    assert not q.try_put(3)          # full -> instant False, never blocks
    assert len(q) == 2
    assert q.get() == 1
    assert q.pop_nowait() == 2
    assert q.pop_nowait() is pf.EMPTY
    assert q.get(timeout_s=0.01) is pf.EMPTY   # bounded timeout, no item
    q.try_put("leftover")
    drained = q.close()
    assert drained == ["leftover"]   # close drains undelivered items
    with pytest.raises(pf.QueueClosed):
        q.try_put("late")            # post-close puts reject instantly
    with pytest.raises(pf.QueueClosed):
        q.get(timeout_s=0.01)        # closed AND drained -> QueueClosed


def test_bounded_queue_blocking_put_unblocks_on_close():
    q = pf.BoundedQueue(1)
    q.try_put("full")
    result = {}

    def blocked_put():
        result["ok"] = q.put("second")   # parks: queue is full

    t = threading.Thread(target=blocked_put, daemon=True)
    t.start()
    time.sleep(0.05)
    assert t.is_alive()              # genuinely blocked on the full queue
    q.close()
    t.join(timeout=5.0)
    assert not t.is_alive()
    assert result["ok"] is False     # closed-under-us returns False


# ----------------------------------------------------------- producer

def test_producer_preserves_order_and_ends_cleanly():
    items = list(range(17))
    it = iter(items)
    prod = pf.PrefetchProducer(lambda: next(it), transform=lambda x: x * 10,
                               depth=3)
    # Clean exhaustion: plain StopIteration at the end — list() would raise
    # the PEP 479 RuntimeError the old generator path leaked.
    assert list(prod) == [x * 10 for x in items]
    prod.close()


def test_producer_multiworker_order_matches_source_order():
    items = list(range(40))
    it = iter(items)

    def jittery(x):   # uneven transform latency scrambles completion order
        time.sleep(0.001 * (x % 3))
        return x

    prod = pf.PrefetchProducer(lambda: next(it), transform=jittery,
                               depth=4, workers=3)
    assert list(prod) == items   # emission order == pull order regardless
    prod.close()


def test_producer_depth_bounds_readahead():
    pulled = []

    def pull():
        if len(pulled) >= 50:
            raise StopIteration
        pulled.append(len(pulled))
        return pulled[-1]

    prod = pf.PrefetchProducer(pull, depth=3, workers=1)
    time.sleep(0.3)   # give the producer every chance to race ahead
    # At most depth buffered + one in flight: the queue, not the source,
    # paces the producer.
    assert len(pulled) <= 3 + 1
    assert prod.queue_depth() <= 3
    prod.close()


def test_producer_exception_propagates_in_order():
    def pull():
        if not hasattr(pull, "n"):
            pull.n = 0
        pull.n += 1
        if pull.n == 3:
            raise ValueError("loader exploded")
        return pull.n

    prod = pf.PrefetchProducer(pull, depth=4)
    assert next(prod) == 1
    assert next(prod) == 2     # items before the failure deliver first
    with pytest.raises(ValueError, match="loader exploded"):
        next(prod)             # then the producer's exception, in position
    prod.close()


def test_producer_close_with_blocked_producer_is_prompt():
    release = threading.Event()

    def slow_pull():
        release.wait(10.0)     # a loader parked mid-gather
        return 1

    prod = pf.PrefetchProducer(slow_pull, depth=1)
    time.sleep(0.05)
    t0 = time.perf_counter()
    prod.close(timeout_s=0.5)  # must not wait out the pull
    assert time.perf_counter() - t0 < 5.0
    release.set()              # let the daemon thread exit
    with pytest.raises(pf.QueueClosed):
        next(prod)             # iterating a closed producer says so


def test_producer_wait_telemetry_books_loader_seconds():
    # A prefix of this test's own: the process-wide ``data.*`` counters are
    # shared with every other producer of the process, and under load one
    # that an earlier test closed can still book a batch while this runs.
    prefix = "data.wait_test"
    wait0 = telemetry.counter(f"{prefix}.producer_wait").value
    batches0 = telemetry.counter(f"{prefix}.producer_batches").value

    def slow_pull():
        if not hasattr(slow_pull, "n"):
            slow_pull.n = 0
        if slow_pull.n >= 4:
            raise StopIteration
        slow_pull.n += 1
        time.sleep(0.02)
        return slow_pull.n

    prod = pf.PrefetchProducer(slow_pull, depth=2, metric_prefix=prefix)
    assert len(list(prod)) == 4
    prod.close()
    waited = telemetry.counter(f"{prefix}.producer_wait").value - wait0
    assert waited >= 4 * 0.02 * 0.5   # the loader seconds are BOOKED
    assert telemetry.counter(f"{prefix}.producer_batches").value \
        - batches0 == 4


# ------------------------------------------------- device feed parity

def test_device_prefetch_bit_identical_to_sync_per_step():
    K = 8
    batches = [_batch_fn(i) for i in range(K)]

    runner_a, state_a = _session()
    for b in batches:
        state_a, _ = runner_a.run(state_a, b)

    runner_b, state_b = _session()
    feed = device_prefetch(iter(batches), runner_b, depth=3)
    n = 0
    for sharded in feed:
        state_b, _ = runner_b.run(state_b, sharded)
        n += 1
    feed.close()
    assert n == K                      # exhaustion ends cleanly, no drop
    _assert_trees_equal(state_a.params, state_b.params)


def test_device_prefetch_unroll_blocks_bit_identical():
    K, U = 8, 2
    batches = [_batch_fn(i) for i in range(K)]

    runner_a, state_a = _session()
    for b in batches:
        state_a, _ = runner_a.run(state_a, b)

    runner_b, state_b = _session()
    feed = device_prefetch(iter(batches), runner_b, depth=2, unroll=U)
    n_blocks = 0
    for block in feed:
        assert isinstance(block, BatchBlock) and len(block) == U
        state_b, _ = runner_b.run_many(state_b, block)
        n_blocks += 1
    feed.close()
    assert n_blocks == K // U
    _assert_trees_equal(state_a.params, state_b.params)


def test_device_prefetch_unroll_drops_partial_remainder():
    """7 batches at unroll=2: three full blocks, the 1-batch remainder is
    dropped (logged) and iteration ends cleanly instead of crashing."""
    batches = [_batch_fn(i) for i in range(7)]
    runner, _ = _session()
    feed = device_prefetch(iter(batches), runner, depth=2, unroll=2)
    blocks = list(feed)
    feed.close()
    assert len(blocks) == 3
    assert all(len(b) == 2 for b in blocks)


def test_train_prefetch_bit_identical_both_loops():
    """train(prefetch_depth=K) vs the synchronous feed: bit-identical final
    params through BOTH loops (per-step and unroll=K blocks), with eval
    cadence forcing clipped blocks on the unrolled path."""
    steps = 12

    def run(prefetch_depth, unroll):
        runner, _ = _session()
        evals = []
        state = train(runner, _params(), _batch_fn, steps, log_every=4,
                      unroll=unroll, prefetch_depth=prefetch_depth,
                      eval_every=5, eval_batch=_batch_fn(999),
                      on_eval=lambda s, v: evals.append(s))
        return jax.device_get(runner.logical_params(state)), evals

    base_1, evals_base1 = run(0, 1)
    pf_1, evals_pf1 = run(3, 1)
    _assert_trees_equal(base_1, pf_1)
    assert evals_pf1 == evals_base1    # cadence points unchanged

    base_u, evals_baseu = run(0, 4)
    pf_u, evals_pfu = run(3, 4)
    _assert_trees_equal(base_u, pf_u)
    _assert_trees_equal(base_1, base_u)
    assert evals_pfu == evals_baseu    # blocks clip at the same boundaries


def test_train_prefetch_iterable_exhaustion_matches_sync():
    """A finite iterable ends the prefetched run at the same step as the
    synchronous run (and the producer's readahead never trains extra
    steps)."""
    def run(prefetch_depth):
        runner, _ = _session()
        state = train(runner, _params(),
                      iter([_batch_fn(i) for i in range(9)]), 50,
                      log_every=0, prefetch_depth=prefetch_depth)
        return int(state.step), jax.device_get(runner.logical_params(state))

    steps_sync, params_sync = run(0)
    steps_pf, params_pf = run(2)
    assert steps_pf == steps_sync == 9
    _assert_trees_equal(params_sync, params_pf)


def test_meter_sizing_folds_microbatched_leaves():
    """The prefetched per-step loop meters the TRANSFORMED batch; under
    gradient accumulation its MicroBatched [k, B/k] leaves must still size
    the meter at B (examples/s would otherwise under-report by B/k)."""
    from autodist_tpu.training import _make_meter

    runner, _ = _session(accum=2)
    sharded = runner.shard_batch(_batch_fn(0))
    assert _make_meter(sharded, None, 1).batch_size == BATCH
    assert _make_meter(_batch_fn(0), None, 1).batch_size == BATCH


def test_native_loader_next_after_close_raises_cleanly():
    """A native loader closed under an async producer: next() during AND
    after the close raises the documented error (never falls into the
    uninitialized numpy-fallback branch)."""
    data = {"x": np.arange(16, dtype=np.float32).reshape(8, 2)}
    dl = DataLoader(data, batch_size=2, shuffle=False)
    if not dl.is_native:
        pytest.skip("no native toolchain in this environment")
    dl.next()
    dl.close()
    with pytest.raises(RuntimeError, match="shut down"):
        dl.next()


def test_train_adopts_tuned_plan_prefetch_depth():
    """train(prefetch_depth=None) adopts a tuned plan's nonzero depth: the
    producer runs (data.producer_batches advances)."""
    from autodist_tpu.strategy.autotune import TunedPlan

    runner, _ = _session()
    runner.tuned_plan = TunedPlan(builder_spec={"name": "AllReduce"},
                                  unroll=1, prefetch_depth=2)
    before = telemetry.counter("data.producer_batches").value
    train(runner, _params(), _batch_fn, 4, log_every=0)
    # The producer pulled every consumed batch (it may have pulled up to
    # depth further ahead before close — readahead, not extra training).
    assert telemetry.counter("data.producer_batches").value - before >= 4


# ------------------------------------------------- per-host sharding

def test_host_shard_rows_disjoint_and_complete():
    n, procs = 96, 4
    seen = []
    blocks = []
    for pid in range(procs):
        start, stop = pf.host_shard_rows(n, pid, procs)
        blocks.append((start, stop))
        seen.extend(range(start, stop))
    assert sorted(seen) == list(range(n))          # disjoint AND complete
    assert all(b[1] - b[0] == n // procs for b in blocks)
    with pytest.raises(ValueError, match="tile"):
        pf.host_shard_rows(10, 0, 3)               # non-divisible refused
    with pytest.raises(ValueError, match="out of"):
        pf.host_shard_rows(8, 4, 4)


def test_train_prefetch_never_calls_source_past_steps():
    """The producer's readahead must stay inside the run's contract: a
    callable source is never invoked with a step index >= steps."""
    calls = []

    def src(i):
        calls.append(i)
        return _batch_fn(i)

    runner, _ = _session()
    train(runner, _params(), src, 6, log_every=0, prefetch_depth=3)
    assert calls == list(range(6))     # every step once, none past the end


def test_host_shard_refuses_ambiguous_batch_dim():
    """Two equally common leading dims: refuse to guess (the runner's
    rule), resolve explicitly with batch_rows=."""
    batch = {"x": np.zeros((32, 2), np.float32),
             "neg": np.zeros((64, 3), np.float32)}
    with pytest.raises(ValueError, match="ambiguous"):
        pf.host_shard(batch, 0, 2)
    s = pf.host_shard(batch, 0, 2, batch_rows=32)
    assert s["x"].shape[0] == 16 and s["neg"].shape[0] == 64


def test_host_shard_slices_batch_leaves_only():
    batch = {"x": np.arange(32).reshape(8, 4), "y": np.arange(8),
             "aux": np.arange(3)}                  # non-batch leaf
    shards = [pf.host_shard(batch, pid, 2) for pid in range(2)]
    np.testing.assert_array_equal(
        np.concatenate([s["x"] for s in shards]), batch["x"])
    np.testing.assert_array_equal(
        np.concatenate([s["y"] for s in shards]), batch["y"])
    for s in shards:                               # aux replicates whole
        np.testing.assert_array_equal(s["aux"], batch["aux"])


def test_assemble_global_batch_matches_shard_batch():
    """Single-process identity: assembling from 'local' rows (the whole
    batch at process 0 of 1) is bit-identical to the runner's shard_batch
    placement — the per-host path and the classic path share one feed
    layout."""
    runner, state = _session()
    batch = _batch_fn(3)
    local = pf.host_shard(batch, 0, 1)
    assembled = pf.assemble_global_batch(runner, local)
    direct = runner.shard_batch(batch)
    _assert_trees_equal(assembled, direct)
    # And it trains: the assembled batch is a valid feed.
    state2, loss_a = runner.run(state, assembled)
    layout = runner.feed_layout()
    assert layout.dp >= 1 and layout.accum == 1


def test_assemble_global_batch_refuses_accumulation():
    runner, _ = _session(accum=2)
    with pytest.raises(ValueError, match="accumulation"):
        pf.assemble_global_batch(runner, _batch_fn(0))


# -------------------------------------------------- flags + autotuner

def test_prefetch_flags_registered_and_typed():
    assert "AUTODIST_PREFETCH_DEPTH" in const.KNOWN_FLAGS
    assert "AUTODIST_PREFETCH_WORKERS" in const.KNOWN_FLAGS
    assert isinstance(const.ENV.AUTODIST_PREFETCH_DEPTH.val, int)
    assert isinstance(const.ENV.AUTODIST_PREFETCH_WORKERS.val, int)
    assert pf.default_prefetch_depth() == 0        # sync feed by default
    assert pf.default_prefetch_workers() >= 1


def test_autotuner_enumerates_and_prices_prefetch_depth():
    """With a declared loader cost the candidate space crosses
    prefetch_depth, the cost model prices the residual data wait
    (max(0, loader_s - hidden_s)), depth-on candidates rank ahead of
    their depth-0 twins, and the knob rides TunedPlan/knobs_dict into
    the applied-plan manifest."""
    from autodist_tpu.model_spec import ModelSpec
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.strategy.autotune import (DEFAULT_CALIBRATION,
                                                TunedPlan,
                                                enumerate_candidates)
    from autodist_tpu.telemetry import costmodel, profiling

    spec = ModelSpec(_params(), sparse_names=())
    rs = ResourceSpec(None)
    plain = enumerate_candidates(spec, rs, optax.sgd(0.1))
    assert all(c.prefetch_depth == 0 for c in plain)   # no loader: no knob
    cands = enumerate_candidates(spec, rs, optax.sgd(0.1),
                                 loader_s_per_step=0.004, budget=64)
    depths = {c.prefetch_depth for c in cands if not c.asynchronous}
    assert depths == {0, 2}                            # the knob enumerated
    assert any("pf=2" in c.name for c in cands)

    # Pricing: a loader slower than everything the pipeline can hide
    # behind leaves a residual; depth >= 1 hides hidden_s of it.
    rec = {"flops": 1e9, "bytes_accessed": 1e8, "steps": 1, "dispatches": 1}
    p0 = costmodel.predict(rec, DEFAULT_CALIBRATION,
                           loader_s_per_step=0.5, prefetch_depth=0)
    p2 = costmodel.predict(rec, DEFAULT_CALIBRATION,
                           loader_s_per_step=0.5, prefetch_depth=2)
    assert p0["breakdown"]["data_wait_s"] == pytest.approx(0.5)
    hidden = (p0["breakdown"]["compute_s"] + p0["breakdown"]["host_s"]
              + p0["breakdown"]["comm_s"])
    assert hidden < 0.5   # the probe program is far cheaper than the loader
    assert p2["breakdown"]["data_wait_s"] == pytest.approx(0.5 - hidden)
    assert p2["step_s"] < p0["step_s"]
    assert p0["bound"] == "data_wait"

    # The knob round-trips the plan record and lands in the applied-plan
    # manifest (what flight-recorder snapshots and adprof diffs read).
    plan = TunedPlan(builder_spec={"name": "AllReduce"}, unroll=4,
                     prefetch_depth=2)
    assert plan.knobs_dict()["prefetch_depth"] == 2
    assert "pf=2" in plan.name
    assert TunedPlan.from_dict(plan.to_dict()).prefetch_depth == 2
    prior = profiling.applied_plan()
    try:
        profiling.set_applied_plan(dict(plan.to_dict(), name=plan.name))
        recorded = profiling.profile_document()["plan"]
        assert recorded["knobs"]["prefetch_depth"] == 2
    finally:
        profiling.set_applied_plan(prior)


def test_serving_staging_rides_bounded_queue():
    """The serving batcher's admission queue IS the input-plane queue core
    (one staging implementation): full -> instant rejection, close ->
    drained requests fail back."""
    from autodist_tpu.serving.batcher import (Batcher, ServeConfig,
                                              ServeError)

    class _Engine:
        capacity = 1
        buckets = (8,)
        max_len = 16

        def admit(self, slot, prompt, key):
            return 1

        def step(self, keys):
            return np.ones(1, np.int32)

        def free(self, slot):
            pass

        def make_keys(self, seed, n):
            return None

    b = Batcher(_Engine(), ServeConfig(max_batch=1, max_queue=2),
                start=False)
    assert isinstance(b._waiting, pf.BoundedQueue)
    b.submit(np.array([1], np.int32), 1)
    b.submit(np.array([1], np.int32), 1)
    with pytest.raises(ServeError, match="full"):
        b.submit(np.array([1], np.int32), 1)       # instant, bounded
    b.close()
    with pytest.raises(ServeError, match="shutting down"):
        b.submit(np.array([1], np.int32), 1)       # closed queue rejects

    # max_queue=0 stays a valid reject-everything (drain) configuration.
    drain = Batcher(_Engine(), ServeConfig(max_batch=1, max_queue=0),
                    start=False)
    with pytest.raises(ServeError, match="full"):
        drain.submit(np.array([1], np.int32), 1)
    drain.close()
