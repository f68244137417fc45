"""Loop + runner: the median ``train.boundary`` span less its two children,
in milliseconds, over the boundaries before the profiler came on: the log
line, the gauges and the bookkeeping every run pays between the read-back
and the next feed. What only a traced run pays there
(``train.boundary.planes``) and the caller's callback
(``train.boundary.on_metrics``) go to standard error beside it. None from a
program without the span (``benchmark/boundary_spans.py``)."""

from benchmark import boundary_spans, harness


def read(record):
    cutoff = boundary_spans.profiler_on_ns(record.get("boundaries") or [])
    parts = boundary_spans.boundary_parts_ms(boundary_spans.program_spans(),
                                             cutoff)
    if not parts:
        return None
    own, planes, callback = (boundary_spans.median(column)
                             for column in zip(*parts))
    harness.log(f"log boundary, median of {len(parts)}: self {own:.3f} ms, "
                f"train.boundary.planes {planes:.3f} ms, "
                f"train.boundary.on_metrics {callback:.3f} ms")
    return own
