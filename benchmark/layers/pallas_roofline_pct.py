"""Kernels: the least time the chip could take for the Pallas calls of the
traced steps (``benchmark/flops.py``: operations over peak FLOP/s or bytes
over peak bytes/s, whichever is larger) over the time they took. Both
kernels together; ``bound`` in the run's ``checks`` says which limit
holds."""


def read(record):
    trace, steps = record.get("trace"), record.get("trace_steps")
    cost, peaks = record.get("kernel_cost_per_step"), record.get("peaks")
    if trace is None or not steps or cost is None or peaks is None:
        return None
    measured = trace.mean("pallas_s") * len(trace.devices)   # all chips' kernels
    if measured <= 0:
        return None
    return 100.0 * cost.least_seconds(peaks) * steps / measured
