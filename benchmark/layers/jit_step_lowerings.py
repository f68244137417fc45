"""Compile + cache: how many times the runner's step program went through
the lowering stage in this process (gauge ``jit.step.lowerings``: 1 is the
least; the call path, ``compiled_step`` and the cost probe each ask for a
lowering, and this counts the ones JAX's own caches did not spare). Moves
``setup_s``. None from a program that keeps no table of programs."""

from benchmark import program_counters


def read(record):
    return program_counters.value("jit.step.lowerings")
