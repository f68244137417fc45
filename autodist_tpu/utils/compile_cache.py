"""Where JAX's persistent compilation cache lives.

A chip run starts on a fresh machine and the flagship step takes tens of
seconds to compile, so compiled programs are kept on disk. The directory is
chosen from outside: ``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself,
wins and this module then sets no directory. Otherwise the cache goes to one
fixed path inside the checkout — the path is part of the cache key, so a
directory made from a temporary name, a process id or the time never hits.
"""

import os
from typing import Mapping, Optional

from autodist_tpu import telemetry

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# jax.monitoring's events for the three stages of getting a program (jax
# 0.9.0, jax/_src/dispatch.py) -> the registry counter each is summed in. The
# backend stage is a compile or a load from the persistent cache.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
JIT_STAGE_COUNTERS = {
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower_s",
    "/jax/core/compile/backend_compile_duration": "jit.backend_s",
}
_listening = False
# Traces nest (a jitted function traced inside another's trace reports its
# own duration, inner first), so a plain sum counts the inner ones twice: on
# the chip 50.5 s "traced" in a 46 s set-up (my chip run, PR 23). The spans
# counted so far that no later one has enclosed yet, newest last.
_counted_traces = []
_MAX_COUNTED_TRACES = 4096


def _on_jit_stage(event: str, duration: float, **_):
    name = JIT_STAGE_COUNTERS.get(event)
    if name is None:
        return
    telemetry.counter(name).inc(duration)
    if name == "jit.backend_s":
        telemetry.counter("jit.programs").inc()


def _on_trace_span(event: str, start: float, end: float, **_):
    """``jit.trace_s`` as the union of the trace spans: an enclosing span
    takes back what the spans inside it had added."""
    if event != TRACE_EVENT:
        return
    added = end - start
    while _counted_traces and _counted_traces[-1][0] >= start:
        inner_start, inner_end = _counted_traces.pop()
        added -= inner_end - inner_start
    _counted_traces.append((start, end))
    del _counted_traces[:-_MAX_COUNTED_TRACES]
    telemetry.counter("jit.trace_s").inc(max(added, 0.0))


def listen_for_jit_stages():
    """Sum every program's tracing, lowering and backend (compile or cache
    load) seconds into ``jit.trace_s``, ``jit.lower_s``, ``jit.backend_s`` and
    count the programs in ``jit.programs``, whether or not telemetry is on:
    a few counter increments a program, none a step. Registers its two
    ``jax.monitoring`` listeners once."""
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
    ``jax.distributed`` where that is used)."""
    import jax
    listen_for_jit_stages()
    if jax.default_backend() == "cpu":
        return None
    path = cache_dir(os.environ, CHECKOUT_ROOT)
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    # Default 1 s would drop the kernels' one-to-few-second compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
