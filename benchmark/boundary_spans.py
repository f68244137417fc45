"""The log boundary as the program's own spans tell it.

At a log boundary the loop reads the loss back (``train.readback_wait``: the
device has finished everything dispatched), runs the boundary block
(``train.boundary``: the log line, gauges and, with telemetry on, the planes
under ``train.boundary.planes``; then the caller's callback under
``train.boundary.on_metrics``), fetches the next batch and dispatches the
next step (``runner.run.dispatch``). From the end of the read-back to the
end of that dispatch the device has nothing to run: the host time between
the two is a lower bound on its idle there.

Spans are rows of ``telemetry.snapshot_spans()``: ``(name, tid, t0_ns,
dur_ns, args)`` on ``time.perf_counter_ns``'s clock, which is also the clock
of the job's ``boundaries``. Only boundaries before the profiler came on
count: from then on the callback and the spans carry the profiler's cost.
"""

import statistics

READBACK = "train.readback_wait"
DISPATCH = ("runner.run.dispatch", "runner.run_many.dispatch")
BOUNDARY = "train.boundary"
PLANES = "train.boundary.planes"
ON_METRICS = "train.boundary.on_metrics"


def program_spans():
    """The program's span ring, still live in the process after the run."""
    from autodist_tpu import telemetry
    return telemetry.snapshot_spans()


def profiler_on_ns(boundaries) -> float:
    """When the boundary at which the job switched the profiler on began its
    callback (the last one still recorded as armed, if a later one is not),
    in nanoseconds; infinity where the profiler never came on."""
    for before, after in zip(boundaries, boundaries[1:]):
        if before[3] == "armed" and after[3] != "armed":
            return before[1] * 1e9
    return float("inf")


def _ends(spans, names):
    return sorted(t0 + dur for name, _tid, t0, dur, _args in spans
                  if name in names)


def gaps_ms(spans, cutoff_ns: float = float("inf")):
    """Per log boundary, milliseconds from the end of ``train.readback_wait``
    to the end of the next dispatch. The first read-back is dropped (the
    meter's warm-up step, and whatever the first period carries); a gap
    counts while its dispatch ended before ``cutoff_ns``."""
    dispatches = _ends(spans, DISPATCH)
    out, i = [], 0
    for end in _ends(spans, (READBACK,))[1:]:
        while i < len(dispatches) and dispatches[i] <= end:
            i += 1
        if i == len(dispatches) or dispatches[i] >= cutoff_ns:
            break
        out.append((dispatches[i] - end) * 1e-6)
    return out


def boundary_parts_ms(spans, cutoff_ns: float = float("inf")):
    """Per ``train.boundary`` span that ended before ``cutoff_ns``:
    ``(self, planes, on_metrics)`` milliseconds, self being the span less
    the two children that began inside it."""
    children = [(name, t0, dur) for name, _tid, t0, dur, _args in spans
                if name in (PLANES, ON_METRICS)]
    out = []
    for name, _tid, t0, dur, _args in spans:
        if name != BOUNDARY or t0 + dur >= cutoff_ns:
            continue
        inside = {PLANES: 0.0, ON_METRICS: 0.0}
        for child, c0, cdur in children:
            if t0 <= c0 and c0 + cdur <= t0 + dur:
                inside[child] += cdur
        out.append(((dur - inside[PLANES] - inside[ON_METRICS]) * 1e-6,
                    inside[PLANES] * 1e-6, inside[ON_METRICS] * 1e-6))
    return out


def median(values):
    return statistics.median(values) if values else None
