"""The set-up ledger: ``telemetry.phase``, the jit stages by program name,
and what reads them.

- a phase books its inclusive seconds under its name and its self seconds
  under ``<name>.self``, with telemetry off; phases and jit stages inside it
  come out of its self time, so the ``.self`` counters and ``jit.wall_s`` add
  up to wall time;
- the ``jax.monitoring`` listeners keep trace / lower / backend seconds by
  program name (a nested trace once, under the outermost program), in at most
  48 rows + ``other``, and the runner's step programs also in ``jit.step.*``;
- ``ops/named_call.py`` counts traced kernel call sites;
- ``train()`` closes ``setup.train_enter_s`` before its loop's first pull and
  freezes ``setup.booked_s``; ``setup_report()`` reads it all back from a snapshot;
- each new reader under ``benchmark/layers/`` returns None from a registry
  that holds nothing and the counter's number from one that does.

Pure in-process tests on the CPU mesh (kernels in interpret mode).
"""

import importlib.util
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import AutoDist, telemetry, train
from autodist_tpu.strategy import AllReduce
from autodist_tpu.telemetry import metrics
from autodist_tpu.telemetry.spans import _NULL_SPAN
from autodist_tpu.utils import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW_READERS = ("jit_step_trace_lower_s", "jit_step_lowerings",
               "jit_step_backend_s", "jit_other_programs_s",
               "kernel_call_sites", "strategy_build_s", "sharding_plan_s",
               "cost_probe_s", "train_enter_s", "expert_bias_balance_s",
               "setup_booked_pct")


@pytest.fixture(autouse=True)
def _fresh_ledger(monkeypatch):
    """A registry and a program table of the test's own, telemetry off."""
    telemetry.disable()
    telemetry.clear()
    monkeypatch.setattr(metrics, "_REGISTRY", metrics.Registry())
    monkeypatch.setattr(compile_cache, "_programs", {})
    monkeypatch.setattr(compile_cache, "_step_programs", set())
    compile_cache.configure()
    yield
    telemetry.disable()
    telemetry.clear()


def _value(name):
    instrument = telemetry.registry().get(name)
    return None if instrument is None else instrument.value


def _linear_session():
    params = {"w": np.ones((4, 1), np.float32), "b": np.zeros((1,), np.float32)}
    loss = lambda p, b: jnp.mean((b["y"] - (b["x"] @ p["w"] + p["b"])) ** 2)  # noqa: E731
    batch = {"x": np.ones((32, 4), np.float32), "y": np.ones((32, 1), np.float32)}
    runner = AutoDist(strategy_builder=AllReduce()).create_distributed_session(
        loss, params, optax.sgd(0.01), example_batch=batch)
    return runner, params, batch


def _self_sum():
    return sum(v for k, v in telemetry.snapshot().items()
               if k.endswith(".self")) + (_value("jit.wall_s") or 0.0)


# ------------------------------------------------------------------ phase

def test_phase_books_inclusive_and_self_seconds_with_telemetry_off():
    assert not telemetry.enabled()
    with telemetry.phase("setup.outer_s"):
        time.sleep(0.02)
        with telemetry.phase("setup.inner_s"):
            time.sleep(0.03)
        time.sleep(0.01)
    outer, inner = _value("setup.outer_s"), _value("setup.inner_s")
    assert inner >= 0.03 and outer >= inner + 0.03
    assert _value("setup.inner_s.self") == pytest.approx(inner)
    assert _value("setup.outer_s.self") == pytest.approx(outer - inner)
    assert telemetry.snapshot_spans() == []        # counters only: no span


def test_disabled_span_stays_the_shared_null_span():
    """``phase`` is beside ``span``, not in its fast path."""
    assert telemetry.span("train.dispatch") is _NULL_SPAN
    with telemetry.phase("setup.some_s"):
        assert telemetry.span("train.dispatch") is _NULL_SPAN
    assert _value("setup.some_s") is not None


def test_phase_is_a_span_of_its_name_when_enabled():
    telemetry.enable()
    with telemetry.phase("setup.outer_s"):
        with telemetry.phase("setup.inner_s"):
            pass
    names = [s[0] for s in telemetry.snapshot_spans()]
    assert names == ["setup.inner_s", "setup.outer_s"]
    assert _value("setup.outer_s.self") is not None


def test_self_seconds_add_up_to_the_outer_phases_wall_time():
    """Three levels, siblings, and a real compile inside: every second of the
    outer phase is in exactly one ``.self`` counter or in ``jit.wall_s``."""
    t0 = time.perf_counter()
    with telemetry.phase("setup.a_s"):
        time.sleep(0.01)
        with telemetry.phase("setup.b_s"):
            with telemetry.phase("setup.c_s"):
                time.sleep(0.01)
            jax.jit(lambda x: x * 5 - 2)(jnp.arange(11.0)).block_until_ready()
        with telemetry.phase("setup.c_s"):        # the same name again
            time.sleep(0.01)
    wall = time.perf_counter() - t0
    assert _value("setup.a_s") == pytest.approx(wall, rel=0.01)
    assert _self_sum() == pytest.approx(_value("setup.a_s"), rel=0.01)


def test_a_compile_inside_a_phase_leaves_its_self_time_without_the_jit_seconds():
    x = jnp.arange(13.0)                              # a program of its own
    stages = ("jit.wall_s", "jit.trace_s", "jit.lower_s", "jit.backend_s")
    before = {n: _value(n) or 0.0 for n in stages}
    with telemetry.phase("setup.compiling_s"):
        jax.jit(lambda x: jnp.sin(x) * 7 + 3)(x).block_until_ready()
    inclusive = _value("setup.compiling_s")
    own = _value("setup.compiling_s.self")
    jit_s, traced, lowered, backend = (_value(n) - before[n] for n in stages)
    assert jit_s > 0 and backend > 0
    assert own == pytest.approx(inclusive - jit_s, abs=1e-4)
    assert own < inclusive / 2                        # most of it was the jit's
    # the stages' own sums hold the same seconds (no stage nests here)
    assert jit_s == pytest.approx(traced + lowered + backend, rel=0.05)


def test_a_phase_opened_in_the_past_takes_what_closed_since():
    t0 = time.perf_counter()
    with telemetry.phase("setup.early_s"):
        time.sleep(0.02)
    time.sleep(0.01)
    with telemetry.phase("setup.import_like_s", since=t0):
        pass
    whole, early = _value("setup.import_like_s"), _value("setup.early_s")
    assert whole >= 0.03
    assert _value("setup.import_like_s.self") == pytest.approx(whole - early)


def test_threads_keep_their_own_intervals():
    """What another thread closes meanwhile is not inside this thread's
    phase: nothing is taken out of its self time."""
    def other():
        with telemetry.phase("setup.other_thread_s"):
            time.sleep(0.02)

    with telemetry.phase("setup.this_thread_s"):
        thread = threading.Thread(target=other)
        thread.start()
        thread.join(timeout=30)
        assert not thread.is_alive()
    assert _value("setup.other_thread_s") >= 0.02
    assert _value("setup.this_thread_s.self") == pytest.approx(
        _value("setup.this_thread_s"))


# ---------------------------------------------------------- programs by name

def test_program_name_meets_across_the_stages():
    name = compile_cache.program_name
    assert name("step_fn") == name("jit(step_fn)") == name("jit_step_fn") \
        == name("pjit_step_fn") == "step_fn"
    assert name("jit(<lambda>)") == name("<lambda>") == "lambda"
    assert name("pmap(f)") == "f" and name("") == "unnamed"


def test_nested_trace_is_booked_once_and_to_the_outer_program():
    @jax.jit
    def ledger_inner(x):
        return jnp.tanh(x) * 2

    @jax.jit
    def ledger_outer(x):
        return ledger_inner(x) + ledger_inner(x * 3)

    before = _value("jit.trace_s") or 0.0
    ledger_outer(jnp.arange(6.0)).block_until_ready()
    report = telemetry.setup_report()
    rows = {row["name"]: row for row in report["programs"]}
    outer = rows["ledger_outer"]
    assert outer["traces"] == outer["lowerings"] == outer["backends"] == 1
    assert outer["cache_loads"] == 0
    assert outer["trace_s"] > 0 and outer["lower_s"] > 0 and outer["backend_s"] > 0
    # the inner function was traced, but its seconds moved to the outer row
    inner = compile_cache._programs["ledger_inner"]
    assert inner["traces"] >= 1 and inner["trace_s"] == pytest.approx(0.0, abs=1e-9)
    assert "ledger_inner" not in rows                 # never a program of its own
    # and the table's traces are the sum's: nothing counted twice
    traced = sum(p["trace_s"] for p in compile_cache._programs.values())
    assert traced == pytest.approx(_value("jit.trace_s") - before, rel=1e-6)


def test_the_table_stops_at_48_names_and_other():
    event = "/jax/core/compile/backend_compile_duration"
    for i in range(compile_cache.MAX_PROGRAMS + 7):
        compile_cache._on_jit_stage(event, 0.5, fun_name=f"jit(prog_{i})")
    report = telemetry.setup_report()
    rows = {row["name"]: row for row in report["programs"]}
    assert len(rows) == compile_cache.MAX_PROGRAMS + 1
    assert rows["other"]["backends"] == 7
    assert rows["other"]["backend_s"] == pytest.approx(3.5)
    assert rows["prog_0"]["backend_s"] == pytest.approx(0.5)
    # a step program registered late still gets its own row
    compile_cache.register_step_programs("late_step")
    compile_cache._on_jit_stage(event, 0.25, fun_name="jit(late_step)")
    assert _value("jit.step.backend_s") == pytest.approx(0.25)
    assert _value("jit.program.late_step.backend_s") == pytest.approx(0.25)


def test_functions_only_traced_inside_others_take_no_row():
    event = compile_cache.TRACE_EVENT
    base = time.time() + 1e6
    for i in range(100):                              # jnp functions in a model
        compile_cache._on_trace_span(event, base + i, base + i + 0.001,
                                     fun_name=f"tiny_{i}")
    compile_cache._on_trace_span(event, base - 1, base + 101, fun_name="model")
    names = {row["name"] for row in telemetry.setup_report()["programs"]}
    assert names == {"model"}
    assert _value("jit.program.model.trace_s") == pytest.approx(102.0)


def test_a_cache_load_is_told_from_a_compile():
    backend = "/jax/core/compile/backend_compile_duration"
    compile_cache._on_jit_stage(compile_cache.CACHE_RETRIEVAL_EVENT, 0.1)
    compile_cache._on_jit_stage(backend, 0.3, fun_name="jit(loaded)")
    compile_cache._on_jit_stage(backend, 2.0, fun_name="jit(compiled)")
    assert _value("jit.program.loaded.cache_loads") == 1
    assert _value("jit.program.compiled.cache_loads") is None
    assert _value("jit.program.compiled.backends") == 1
    assert _value("jit.programs") == 2


def test_step_lowerings_count_the_runners_step_and_no_other_program():
    """``runner.run`` then ``compiled_step``: the gauge agrees with a listener
    of the test's own on the same events."""
    from jax import monitoring
    seen = []

    def listener(event, duration, fun_name="", **_):
        if event.endswith("jaxpr_to_mlir_module_duration"):
            seen.append(fun_name)

    monitoring.register_event_duration_secs_listener(listener)
    try:
        runner, params, batch = _linear_session()
        state = runner.init(params)
        state, _ = runner.run(state, batch)
        runner.compiled_step(state, runner.shard_batch(batch))
        jax.jit(lambda x: x + 41)(jnp.arange(3.0))    # another program
    finally:
        monitoring.unregister_event_duration_listener(listener)
    steps = [n for n in seen if compile_cache.program_name(n) == "step_fn"]
    assert len(steps) >= 1 and len(seen) > len(steps)
    assert _value("jit.step.lowerings") == len(steps)
    assert _value("jit.step.traces") >= _value("jit.step.lowerings")
    assert _value("jit.step.trace_s") > 0 and _value("jit.step.backend_s") > 0
    assert telemetry.setup_report()["step_programs"] == ["step_fn"]


def test_run_many_is_a_step_program_too():
    runner, params, batch = _linear_session()
    state = runner.init(params)
    state, _ = runner.run(state, batch)
    one = _value("jit.step.lowerings")
    state, _ = runner.run_many(state, [batch, batch])
    assert _value("jit.step.lowerings") == one + 1
    assert telemetry.setup_report()["step_programs"] == ["many_fn", "step_fn"]


def test_kernel_call_sites_are_counted_when_traced_in_interpret_mode():
    from autodist_tpu.ops import flash_attention
    q = jnp.ones((1, 16, 2, 8), jnp.float32)
    jitted = jax.jit(lambda q: flash_attention(
        q, q, q, causal=True).sum())
    jitted(q).block_until_ready()
    assert _value("jit.kernel_call_sites.flash_fwd") == 1
    assert _value("jit.kernel_call_sites") == 1
    jitted(q).block_until_ready()                     # a step: traces nothing
    assert _value("jit.kernel_call_sites") == 1
    jax.jit(jax.grad(lambda q: flash_attention(
        q, q, q, causal=True).sum()))(q).block_until_ready()
    assert _value("jit.kernel_call_sites.flash_fwd") == 2
    assert _value("jit.kernel_call_sites.flash_bwd_dkv") == 1
    assert _value("jit.kernel_call_sites") == 3


@pytest.mark.parametrize("head_dim", [64, 128, 192],
                         ids=["relaid", "in-place", "latent-packed"])
def test_one_traced_pair_books_one_site_a_kernel_whatever_the_layout(head_dim):
    """The layout of a flash call's operands is chosen from their shapes and
    nothing is traced to choose it: a forward + backward pair books one
    ``flash_fwd`` and one ``flash_bwd_dkv`` site where the heads go through
    XLA's transposes (64 wide), where they stay in place (128) and at
    latent attention's widths with the values packed behind the keys."""
    from autodist_tpu.ops import flash_attention
    struct = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.bfloat16)  # noqa: E731
    if head_dim == 192:     # kv as one projection's rows
        operands = (struct(1, 256, 2, 192), struct(1, 256, 2 * 256),
                    struct(1, 256, 64))
        attend = lambda q, kv, ks: flash_attention(  # noqa: E731
            q, kv, None, k_shared=ks, heads=(2, 2))
    elif head_dim == 128:   # q, k, v as their projections' rows
        operands = (struct(1, 256, 2 * 128),) * 3
        attend = lambda q, k, v: flash_attention(q, k, v, heads=(2, 2))  # noqa: E731
    else:
        operands = (struct(1, 256, 2, head_dim),) * 3
        attend = flash_attention
    jax.jit(jax.grad(lambda *a: attend(*a).astype(jnp.float32).sum(),
                     argnums=(0, 1, 2))).trace(*operands)
    assert _value("jit.kernel_call_sites.flash_fwd") == 1
    assert _value("jit.kernel_call_sites.flash_bwd_dkv") == 1
    assert _value("jit.kernel_call_sites") == 2
    assert (_value("flash.fwd.operands_relaid"),
            _value("flash.bwd.operands_relaid")) == {
                64: (4, 7), 128: (0, 0), 192: (1, 2)}[head_dim]


# ------------------------------------------------------- the three old copies

def test_setup_counters_keep_their_names_and_gain_self_seconds():
    names = ("setup.strategy_build_s", "setup.plan_build_s",
             "setup.state_place_s")
    runner, params, batch = _linear_session()
    runner.init(params)
    runner.init(params)                               # every init counts
    assert _value("setup.state_place_calls") == 2
    for name in names:
        assert _value(name) > 0 and _value(name + ".self") is not None, name
    # the strategy file's write is a phase inside the build
    assert 0 < _value("setup.strategy_write_s") <= _value("setup.strategy_build_s")
    # the identity program that places the state ran inside state_place_s
    assert _value("setup.state_place_s.self") < _value("setup.state_place_s")
    assert telemetry.snapshot_spans() == []


def test_function_is_one_phase_over_strategy_plan_and_placement():
    params = {"w": np.ones((4, 1), np.float32)}
    batch = {"x": np.ones((8, 4), np.float32), "y": np.ones((8, 1), np.float32)}
    step = AutoDist(strategy_builder=AllReduce()).function(
        lambda p, b: jnp.mean((b["y"] - b["x"] @ p["w"]) ** 2), params,
        optax.sgd(0.1), example_batch=batch)
    whole = _value("setup.function_s")
    inside = sum(_value(n) for n in ("setup.strategy_build_s",
                                     "setup.plan_build_s",
                                     "setup.state_place_s"))
    assert whole >= inside > 0
    step(batch)                                       # the step is no phase
    assert _value("setup.function_s") == whole


def test_cost_probe_is_a_phase_only_where_it_is_armed():
    runner, params, batch = _linear_session()
    state = runner.init(params)
    runner.run(state, batch)
    assert _value("setup.cost_probe_s") is None       # telemetry off: not armed
    assert runner.plan_costs(params, batch) is not None
    assert _value("setup.cost_probe_s") > 0


def test_expert_bias_balance_books_its_seconds_and_passes():
    from autodist_tpu.models import moe

    class _OneBias:
        def apply(self, variables, tokens, return_hidden, mutable):
            load = jnp.stack([jnp.arange(4.0) + tokens.sum()])
            return None, {"intermediates": {"block_0": {"moe": {"load": (load[0],)}}}}

    params = {"block_0": {"moe": {"expert_bias": jnp.zeros(4)}}}
    tokens = [jnp.ones((1, 4), jnp.int32)]
    out = moe.balance_expert_bias(_OneBias(), params, tokens, [0.05, 0.01, 0.001])
    assert _value("setup.expert_bias_passes") == 3
    assert _value("setup.expert_bias_balance_s") > 0
    assert float(jnp.abs(out["block_0"]["moe"]["expert_bias"]).sum()) > 0


# ------------------------------------------------------- train() and report

def test_train_closes_its_entry_phase_before_the_first_pull():
    runner, params, batch = _linear_session()
    pulled = []

    def batches(i):
        if not pulled:                                # the first pull
            pulled.append((_value("setup.train_enter_s"),
                           _value("setup.booked_s")))
        return batch

    train(runner, params, batches, steps=3, log_every=0, prefetch_depth=0)
    entered, booked = pulled[0]
    assert entered is not None and booked is not None
    assert _value("setup.train_enter_s") == entered   # closed once, then fixed
    assert _value("setup.state_place_calls") == 1     # train()'s own init
    assert entered >= _value("setup.state_place_s")
    # what was frozen is the account as it stood at the pull
    assert booked == pytest.approx(
        telemetry.setup_report()["booked_at_setup_end_s"])
    assert booked <= _self_sum()                      # the step compiled later


def test_train_logs_one_line_from_the_ledger(monkeypatch):
    from autodist_tpu.utils import logging
    lines = []
    monkeypatch.setattr(logging, "info",
                        lambda fmt, *a: lines.append(fmt % a if a else fmt))
    runner, params, batch = _linear_session()
    train(runner, params, [batch] * 4, steps=4, log_every=0, prefetch_depth=2)
    ledger = [l for l in lines if "set-up" in l and "booked" in l]
    assert len(ledger) == 1
    assert "step: trace+lower" in ledger[0] and "other programs:" in ledger[0]
    assert _value("setup.train_enter_s") is not None


def test_setup_report_round_trips_through_a_snapshot():
    runner, params, batch = _linear_session()
    train(runner, params, lambda i: batch, steps=2, log_every=0,
          prefetch_depth=0)
    snap = telemetry.registry().snapshot()
    report = telemetry.setup_report(snap)
    assert report == telemetry.setup_report()
    assert report["booked_s"] == pytest.approx(_self_sum())
    assert report["phases"]["setup.train_enter_s"]["s"] == \
        snap["setup.train_enter_s"]
    assert report["step"]["lowerings"] == snap["jit.step.lowerings"] >= 1
    assert report["jit"]["programs"] == snap["jit.programs"]
    assert {"name", "total_s", "trace_s", "cache_loads"} <= set(
        report["programs"][0])
    # plain data: what the stats opcode ships decodes to the same report
    from autodist_tpu.parallel import wire
    assert telemetry.setup_report(wire.decode(wire.encode(snap))) == report
    assert "booked" in telemetry.format_setup_report(report)


# ---------------------------------------------------------------- readers

def _reader(name):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    path = os.path.join(ROOT, "benchmark", "layers", name + ".py")
    spec = importlib.util.spec_from_file_location(f"_ledger_reader_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_returns_none_on_an_empty_registry(name):
    metrics.registry().clear()
    record = {"end_to_end": {"setup_s": 50.0}}
    assert _reader(name).read(record) is None


def _filled():
    telemetry.gauge("setup.booked_s").set(40.0)
    for name, value in (("setup.strategy_build_s", 0.5),
                        ("setup.strategy_write_s", 0.2),
                        ("setup.plan_build_s", 0.25),
                        ("setup.train_enter_s", 1.5),
                        ("setup.train_enter_s.self", 0.5),
                        ("setup.expert_bias_balance_s", 6.0),
                        ("setup.expert_bias_balance_s.self", 5.0),
                        ("setup.expert_bias_passes", 128),
                        ("jit.trace_s", 20.0), ("jit.lower_s", 10.0),
                        ("jit.backend_s", 8.0), ("jit.programs", 21),
                        ("jit.kernel_call_sites", 12),
                        ("jit.kernel_call_sites.flash_fwd", 12)):
        telemetry.counter(name).inc(value)
    compile_cache.register_step_programs("step_fn")
    compile_cache._book_program("step_fn", trace_s=12.0, lower_s=6.0,
                                backend_s=5.0, traces=3, lowerings=2,
                                backends=1)
    compile_cache._book_program("init", trace_s=3.0, lower_s=1.0,
                                backend_s=2.0, traces=1, lowerings=1,
                                backends=1, cache_loads=1)


@pytest.mark.parametrize("name,expected", [
    ("jit_step_trace_lower_s", 18.0), ("jit_step_lowerings", 2),
    ("jit_step_backend_s", 5.0), ("jit_other_programs_s", 15.0),
    ("kernel_call_sites", 12), ("strategy_build_s", 0.5),
    ("sharding_plan_s", 0.25), ("cost_probe_s", 0.0), ("train_enter_s", 1.5),
    ("expert_bias_balance_s", 6.0), ("setup_booked_pct", 80.0)])
def test_reader_gives_the_counters_number_on_a_filled_registry(name, expected):
    _filled()
    record = {"end_to_end": {"setup_s": 50.0}}
    assert _reader(name).read(record) == pytest.approx(expected)


def test_benchmark_json_lists_each_new_reader_once_under_setup_s():
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW_READERS:
        entry = per_layer[name]
        assert entry["moves"] == "setup_s"
        assert entry["source"] == "program_counter"
        assert entry["layer"] in ("strategy and plan", "compile and cache")
        assert entry["better"] == ("higher" if name == "setup_booked_pct"
                                   else "lower")
    assert per_layer["expert_bias_balance_s"]["workloads"] == [
        "trinity-pretrain-8k", "lfm2-pretrain-8k"]
