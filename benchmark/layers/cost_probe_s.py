"""Compile + cache: the seconds the runner's cost probe took to lower and
compile the step again and read XLA's analysis (counter
``setup.cost_probe_s``). 0 where the probe is not armed (it is armed by the
profiling plane, which no cell turns on during set-up). Moves ``setup_s``.
None from a program without the set-up ledger."""

from benchmark import program_counters


def read(record):
    if program_counters.value("setup.booked_s") is None:
        return None
    return program_counters.value("setup.cost_probe_s") or 0.0
