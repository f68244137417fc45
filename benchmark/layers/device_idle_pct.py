"""Device: 1 - (union of the intervals in which an operation ran) over the
traced window, averaged over the devices."""


def read(record):
    trace = record.get("trace")
    if trace is None:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
