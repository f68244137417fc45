"""Compile + cache: the seconds JAX spent tracing programs to jaxprs and
lowering them to StableHLO over the whole run (``jit.trace_s`` +
``jit.lower_s``, booked by the program from jax.monitoring's events, nested
traces once): the part of a warm set-up the persistent cache cannot remove.
Moves ``setup_s``. None from a program that does not book it."""

from benchmark import program_counters


def read(record):
    if not program_counters.value("jit.programs"):
        return None
    return (program_counters.value("jit.trace_s") or 0.0) \
        + (program_counters.value("jit.lower_s") or 0.0)
