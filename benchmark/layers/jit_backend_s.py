"""Compile + cache: the seconds the backend took to hand over programs over
the whole run (``jit.backend_s``: compiled, or loaded from the persistent
cache; ``jit.programs`` of them, on standard error). Moves ``setup_s``. None
from a program that does not book it."""

from benchmark import harness, program_counters


def read(record):
    programs = program_counters.value("jit.programs")
    if not programs:
        return None
    harness.log(f"jit.programs {programs}")
    return program_counters.value("jit.backend_s")
