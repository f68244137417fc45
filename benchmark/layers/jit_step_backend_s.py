"""Compile + cache: the seconds the backend took to hand over the runner's
step program (gauge ``jit.step.backend_s``: a compile, or a load from the
persistent cache): the step's share of ``jit_backend_s``. Moves ``setup_s``.
None from a program that keeps no table of programs."""

from benchmark import program_counters


def read(record):
    return program_counters.value("jit.step.backend_s")
