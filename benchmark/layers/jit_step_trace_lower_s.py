"""Compile + cache: the seconds JAX spent tracing and lowering the runner's
own step program (gauges ``jit.step.trace_s`` + ``jit.step.lower_s``, the
rows of ``step_fn`` / ``many_fn`` in the program's table of jit stages by
program name; a function traced inside the step's trace counts here, once):
the step's share of ``jit_trace_lower_s``, which the persistent cache cannot
remove. How often each stage ran goes to standard error. Moves ``setup_s``.
None from a program that keeps no such table."""

from benchmark import harness, program_counters


def read(record):
    trace_s = program_counters.value("jit.step.trace_s")
    if trace_s is None:
        return None
    lower_s = program_counters.value("jit.step.lower_s") or 0.0
    harness.log(f"jit.step: trace {trace_s:.3f}s in "
                f"{program_counters.value('jit.step.traces')} trace(s), "
                f"lower {lower_s:.3f}s in "
                f"{program_counters.value('jit.step.lowerings')} lowering(s)")
    return trace_s + lower_s
