"""Sharding: the part of the collectives' union during which no other
operation runs on that device, as a share of the traced window, averaged
over the devices. What overlap with the backward pass did not hide."""


def read(record):
    trace = record.get("trace")
    if trace is None:
        return None
    return 100.0 * trace.mean("exposed_collective_s") / trace.window_s
