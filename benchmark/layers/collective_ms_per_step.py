"""Sharding: the union of collective operations (all-reduce,
reduce-scatter, all-gather, all-to-all, collective-permute) on a device
inside the traced window, averaged over the devices, in milliseconds an
optimizer step. 0 on one chip."""


def read(record):
    trace, steps = record.get("trace"), record.get("trace_steps")
    if trace is None or not steps:
        return None
    return 1e3 * trace.mean("collective_s") / steps
