"""Loop + runner: the program's ``train.dispatch`` spans inside the window,
in milliseconds a step (the median, so that one stall does not stand for
the loop). Host time: it bounds the rate only where the device waits for it,
which ``device_idle_pct`` says."""

import statistics


def read(record):
    spans = record.get("dispatch_spans_ms")
    return statistics.median(spans) if spans else None
