"""Tracing and compilation-stage snapshots.

Parity with reference §5.1:

- Chrome-trace timelines (``runner.py:66-75``, ``/tmp/autodist/traces/...``) map to
  :func:`trace`, a ``jax.profiler.trace`` wrapper writing a Perfetto/TensorBoard
  trace under the working dir's ``traces/``.
- Graph-evolution snapshots (``utils/visualization_util.py:24-36`` wrote the graph
  at each transform stage) map to :func:`dump_stage`: the jaxpr and StableHLO text
  of the train step at each compilation stage, written under ``graphs/<tag>/``.

``trace(..., with_host_spans=True)`` additionally enables the host-side
telemetry spans (:mod:`autodist_tpu.telemetry`) for the traced window. An
enabled span is a ``jax.profiler.TraceAnnotation`` of its name, so the
profiler's own trace holds the program's spans in its host plane, on one clock
with the device planes: open the one ``*.xplane.pb`` (TensorBoard, or
``jax.profiler.ProfileData.from_file``) and host dispatch/wait spans sit next
to device execution. For a CLOCK-ALIGNED multi-worker host timeline use
``telemetry.collect_cluster_trace`` / ``tools/tracedump.py``; see
docs/usage/observability.md.
"""

import contextlib
import itertools
import os
import time
from typing import Optional

from autodist_tpu import const
from autodist_tpu.utils import logging

# Monotonic per-process suffix for default trace dirs: a wall-clock-second
# name alone collides when two traces start within the same second (the
# second trace silently wrote into — and interleaved with — the first's dir).
_TRACE_SEQ = itertools.count()


def _unique_trace_dir(name: str) -> str:
    """Collision-free default trace directory under the working dir."""
    return os.path.join(const.DEFAULT_TRACE_DIR,
                        f"{name}_{int(time.time())}_{next(_TRACE_SEQ):03d}")


@contextlib.contextmanager
def trace(name: str = "trace", trace_dir: Optional[str] = None,
          with_host_spans: bool = False):
    """Profile the enclosed steps: ``with tracing.trace(): runner.run(...)``.

    Produces a Perfetto-compatible trace viewable in TensorBoard or ui.perfetto.dev
    (the chrome-trace timeline counterpart). With ``with_host_spans=True``,
    telemetry span recording is enabled for the window, so the trace's host
    plane carries the program's spans beside the device planes (telemetry
    returns to its prior enabled/disabled state afterwards)."""
    import jax
    trace_dir = trace_dir or _unique_trace_dir(name)
    os.makedirs(trace_dir, exist_ok=True)
    logging.info("Writing profiler trace to %s", trace_dir)
    if with_host_spans:
        from autodist_tpu import telemetry
        was_enabled = telemetry.enabled()
        telemetry.enable()
    try:
        with jax.profiler.trace(trace_dir):
            yield trace_dir
    finally:
        if with_host_spans and not was_enabled:
            telemetry.disable()


def dump_stage(tag: str, stage: str, fn, *example_args,
               dump_dir: Optional[str] = None) -> Optional[str]:
    """Write the jaxpr + StableHLO of ``fn(*example_args)`` for one build stage.

    Stages mirror the reference's four snapshots (0-original, 1-after-partition,
    2-after-in-graph, 3-transformed): here typically "0-original" (user loss fn)
    and "1-distributed" (the sharded train step).
    """
    import jax
    dump_dir = dump_dir or os.path.join(const.DEFAULT_GRAPH_DUMP_DIR, tag)
    os.makedirs(dump_dir, exist_ok=True)
    base = os.path.join(dump_dir, stage)
    try:
        jaxpr = jax.make_jaxpr(fn)(*example_args)
        with open(base + ".jaxpr.txt", "w") as f:
            f.write(str(jaxpr))
        lowered = jax.jit(fn).lower(*example_args)
        with open(base + ".stablehlo.txt", "w") as f:
            f.write(lowered.as_text())
        logging.debug("Dumped %s stage %s", tag, stage)
        return base
    except Exception as e:  # diagnostics must never break training
        logging.warning("Stage dump %s/%s (dump path %s.*) failed: %s",
                        tag, stage, base, e)
        return None
