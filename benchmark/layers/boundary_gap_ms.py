"""Loop + runner: per log boundary, host time from the end of the loss
read-back (``train.readback_wait``) to the end of the next step's dispatch
(``runner.run.dispatch``), in milliseconds; the median over the boundaries
before the profiler came on, the first dropped. The device has nothing to
run in between, so this is a lower bound on its idle at a boundary, taken
where the quoted rate is taken (``benchmark/boundary_spans.py``)."""

from benchmark import boundary_spans


def read(record):
    cutoff = boundary_spans.profiler_on_ns(record.get("boundaries") or [])
    return boundary_spans.median(
        boundary_spans.gaps_ms(boundary_spans.program_spans(), cutoff))
