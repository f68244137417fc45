"""Compile + cache: Pallas kernel call sites traced in this process (counter
``jit.kernel_call_sites``, incremented by ``ops/named_call.py`` when a call
site is traced, never in a step; by kernel on standard error). Every site is
traced and lowered again, so set-up pays for each. 0 in a cell whose model
calls no kernel. Moves ``setup_s``. None from a program without the set-up
ledger."""

from benchmark import harness, program_counters


def read(record):
    if program_counters.value("setup.booked_s") is None:
        return None
    from autodist_tpu import telemetry
    prefix = "jit.kernel_call_sites."
    by_kernel = {name[len(prefix):]: value
                 for name, value in telemetry.snapshot().items()
                 if name.startswith(prefix)}
    harness.log(f"kernel call sites traced: {by_kernel}")
    return program_counters.value("jit.kernel_call_sites") or 0
