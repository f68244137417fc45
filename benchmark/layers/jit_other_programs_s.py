"""Compile + cache: trace + lower + backend seconds of every program that is
not the runner's step (the four sums ``jit.trace_s`` + ``jit.lower_s`` +
``jit.backend_s`` less ``jit.step.*``): weights and batches made on the
device, state placement, the reference check, eager operations. The parts,
and the five dearest programs by name from the program's own table
(``jit.program.<name>.*``), go to standard error. Moves ``setup_s``. None
from a program that keeps no table of programs."""

from benchmark import harness, program_counters


def read(record):
    step = [program_counters.value(f"jit.step.{stage}")
            for stage in ("trace_s", "lower_s", "backend_s")]
    if step[0] is None:
        return None
    total = [program_counters.value(f"jit.{stage}") or 0.0
             for stage in ("trace_s", "lower_s", "backend_s")]
    other = [max(t - (s or 0.0), 0.0) for t, s in zip(total, step)]
    from autodist_tpu import telemetry
    rows = telemetry.setup_report()["programs"]
    in_rows = sum(row["total_s"] for row in rows)
    harness.log(
        f"programs other than the step: trace {other[0]:.3f}s lower "
        f"{other[1]:.3f}s backend {other[2]:.3f}s; {len(rows)} by name hold "
        f"{in_rows:.3f}s, dearest: " + ", ".join(
            f"{row['name']} {row['total_s']:.2f}s (x{row['traces']} traced, "
            f"x{row['lowerings']} lowered, {row['cache_loads']}/"
            f"{row['backends']} loaded)" for row in rows[:5]))
    return sum(other)
