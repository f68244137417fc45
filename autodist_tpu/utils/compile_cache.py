"""Where JAX's persistent compilation cache lives.

A chip run starts on a fresh machine and the flagship step takes tens of
seconds to compile, so compiled programs are kept on disk. The directory is
chosen from outside: ``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself,
wins and this module then sets no directory. Otherwise the cache goes to one
fixed path inside the checkout — the path is part of the cache key, so a
directory made from a temporary name, a process id or the time never hits.
"""

import os
import re
import threading
from typing import Dict, Mapping, Optional

from autodist_tpu import telemetry
from autodist_tpu.telemetry import phases as _phases

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# jax.monitoring's events for the three stages of getting a program (jax
# 0.9.0, jax/_src/dispatch.py) -> the registry counter each is summed in and
# its columns in the per-program table. The backend stage is a compile or a
# load from the persistent cache.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
JIT_STAGE_COUNTERS = {
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower_s",
    "/jax/core/compile/backend_compile_duration": "jit.backend_s",
}
_STAGE_COLUMNS = {"jit.lower_s": ("lower_s", "lowerings"),
                  "jit.backend_s": ("backend_s", "backends")}
# Fired (a duration event) inside the backend stage of a program the
# persistent cache held, just before the stage's own event.
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_listening = False
# Per thread: ``traces``, the trace spans counted so far that no later one
# has enclosed yet, newest last; ``loaded``, whether the backend stage now
# running was a cache load. Traces nest (a jitted function traced inside
# another's trace reports its own duration, inner first), so a plain sum
# counts the inner ones twice: on the chip 50.5 s "traced" in a 46 s set-up
# (my chip run, PR 23).
_tls = threading.local()
_MAX_COUNTED_TRACES = 4096

# The per-program table: trace / lower / backend seconds and how often each
# stage ran, by program name, published as gauges ``jit.program.<name>.*``
# (a trace an outer program's trace encloses moves to the outer's row, so
# rows can shrink: gauges, not counters). A name gets a row in the registry
# once it was lowered or handed to the backend, or its traces add up to
# ``ROW_MIN_TRACE_S`` (the hundreds of jnp functions traced inside a model's
# trace get none); the first ``MAX_PROGRAMS`` such names, the rest share the
# row ``other``.
MAX_PROGRAMS = 48
OTHER_PROGRAMS = "other"
ROW_MIN_TRACE_S = 0.05
_MAX_TRACKED = 2048
_STEP_COLUMNS = ("trace_s", "lower_s", "backend_s", "traces", "lowerings")
_programs: Dict[str, Dict[str, float]] = {}   # name -> columns + "row"
_step_programs = set()
_programs_lock = threading.Lock()
_WRAPPED_NAME = re.compile(r"^(?:p?jit|pmap)\((.*)\)$")


def program_name(fun_name: str) -> str:
    """One name for a program across its stages: the trace event carries the
    function's name (``step_fn``), lower and backend events the module's
    (``jit(step_fn)``, ``jit_step_fn`` in older jax); other characters than
    letters, digits and ``_`` become ``_`` (the name is part of a metric's)."""
    name = str(fun_name or "")
    wrapped = _WRAPPED_NAME.match(name)
    if wrapped:
        name = wrapped.group(1)
    else:
        for prefix in ("pjit_", "jit_"):
            if name.startswith(prefix):
                name = name[len(prefix):]
                break
    return re.sub(r"[^A-Za-z0-9_]+", "_", name).strip("_") or "unnamed"


def register_step_programs(*names: str):
    """The names under which the caller's step programs are traced (the
    runner's ``step_fn`` and ``many_fn``: ``run``, ``run_many``,
    ``compiled_step`` and the cost probe all lower the same function): their
    stages are also summed in ``jit.step.*``."""
    with _programs_lock:
        for name in names:
            name = program_name(name)
            _step_programs.add(name)
            telemetry.gauge(f"jit.program.{name}.is_step").set(1)


def _row_for(name: str, program: Dict[str, float]) -> Optional[str]:
    """The registry row a program's account shows in, or None while it has
    none (see ``MAX_PROGRAMS``)."""
    if name in _step_programs or name == OTHER_PROGRAMS:
        return name
    if not (program["lowerings"] or program["backends"]
            or program["trace_s"] >= ROW_MIN_TRACE_S):
        return None
    named = sum(1 for p in _programs.values()
                if p["row"] not in (None, OTHER_PROGRAMS))
    return name if named < MAX_PROGRAMS else OTHER_PROGRAMS


def _book_program(name: str, **columns: float):
    """Add ``columns`` to a program's account and publish its row (and the
    step sums where it is a step program)."""
    with _programs_lock:
        if name not in _programs and len(_programs) >= _MAX_TRACKED:
            name = OTHER_PROGRAMS
        program = _programs.get(name)
        if program is None:
            program = _programs[name] = dict(
                dict.fromkeys(_phases.PROGRAM_FIELDS, 0), row=None)
        for column, amount in columns.items():
            program[column] += amount
        row = program["row"]
        if row is None:
            row = program["row"] = _row_for(name, program)
            if row is None:
                return
            # a new row starts with all its program has so far
            columns = {c: program[c] for c in _phases.PROGRAM_FIELDS
                       if program[c]}
        shown = program
        if row == OTHER_PROGRAMS and name != OTHER_PROGRAMS:
            shown = _programs.get(OTHER_PROGRAMS)
            if shown is None:
                shown = _programs[OTHER_PROGRAMS] = dict(
                    dict.fromkeys(_phases.PROGRAM_FIELDS, 0),
                    row=OTHER_PROGRAMS)
            for column, amount in columns.items():
                shown[column] += amount
        for column in columns:
            telemetry.gauge(f"jit.program.{row}.{column}").set(shown[column])
        if name in _step_programs:
            for column in _STEP_COLUMNS:
                telemetry.gauge(f"jit.step.{column}").set(sum(
                    _programs[n][column] for n in _step_programs
                    if n in _programs))


def _on_jit_stage(event: str, duration: float, fun_name: str = "", **_):
    if event == CACHE_RETRIEVAL_EVENT:
        _tls.loaded = True
        return
    name = JIT_STAGE_COUNTERS.get(event)
    if name is None:
        return
    telemetry.counter(name).inc(duration)
    telemetry.counter(_phases.JIT_WALL).inc(_phases.close_interval(duration))
    seconds, times = _STAGE_COLUMNS[name]
    columns = {seconds: duration, times: 1}
    if name == "jit.backend_s":
        telemetry.counter("jit.programs").inc()
        if getattr(_tls, "loaded", False):
            columns["cache_loads"] = 1
        _tls.loaded = False
    _book_program(program_name(fun_name), **columns)


def _on_trace_span(event: str, start: float, end: float, fun_name: str = "",
                   **_):
    """``jit.trace_s`` as the union of the trace spans: an enclosing span
    takes back what the spans inside it had added, from the sum and from
    their programs' rows (a nested trace is booked once, to the outermost
    program)."""
    if event != TRACE_EVENT:
        return
    counted = getattr(_tls, "traces", None)
    if counted is None:
        counted = _tls.traces = []
    name = program_name(fun_name)
    added = end - start
    while counted and counted[-1][0] >= start:
        inner_start, inner_end, inner_name = counted.pop()
        added -= inner_end - inner_start
        _book_program(inner_name, trace_s=-(inner_end - inner_start))
    counted.append((start, end, name))
    del counted[:-_MAX_COUNTED_TRACES]
    telemetry.counter("jit.trace_s").inc(max(added, 0.0))
    telemetry.counter(_phases.JIT_WALL).inc(
        _phases.close_interval(end - start))
    _book_program(name, trace_s=end - start, traces=1)


def listen_for_jit_stages():
    """Sum every program's tracing, lowering and backend (compile or cache
    load) seconds into ``jit.trace_s``, ``jit.lower_s``, ``jit.backend_s``,
    count the programs in ``jit.programs``, keep the same by program name in
    the ``jit.program.*`` table, and book each stage's seconds once into the
    set-up ledger (``jit.wall_s``, out of the enclosing phase's self time),
    whether or not telemetry is on: a few registry updates a program, none a
    step. Registers its two ``jax.monitoring`` listeners once."""
    global _listening
    if not _listening:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_jit_stage)
        monitoring.register_event_time_span_listener(_on_trace_span)
        _listening = True


def cache_dir(environ: Mapping[str, str], checkout_root: str) -> Optional[str]:
    """The directory this program has to set, or None where it sets none
    because ``JAX_COMPILATION_CACHE_DIR`` already placed the cache."""
    # graftlint: disable=GL007(JAX's own variable, not an AUTODIST flag; read from the mapping the caller passes so the choice stays a pure function)
    if environ.get(CACHE_DIR_ENV):
        return None
    return os.path.join(checkout_root, ".jax_cache")


def configure() -> Optional[str]:
    """Turn the persistent cache on for accelerator backends; returns the
    directory in use. On the CPU backend it places no cache (returns None):
    the test suite neither writes a cache into the checkout nor changes its
    timing. On every backend it starts the ``jit.*`` set-up counters
    (:func:`listen_for_jit_stages`). Idempotent; call before the first
    compile that should be kept (it initializes the backend, so after
    ``jax.distributed`` where that is used). Its first call is the
    process's first touch of the backend, which on a chip takes seconds:
    ``setup.backend_init_s`` (later calls book microseconds)."""
    with telemetry.phase("setup.import_s"):
        import jax
    listen_for_jit_stages()
    with telemetry.phase("setup.backend_init_s"):
        backend = jax.default_backend()
    if backend == "cpu":
        return None
    path = cache_dir(os.environ, CHECKOUT_ROOT)
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    # Default 1 s would drop the kernels' one-to-few-second compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
