"""Unified runtime telemetry: host-side span tracing, a process-global
metrics registry, and the exporters feeding the cross-worker stats plane.

Three planes, one subsystem (docs/usage/observability.md):

- **Spans** (:mod:`autodist_tpu.telemetry.spans`) — ``telemetry.span("name")``
  context manager / ``@telemetry.traced()`` decorator recording a host
  timeline into a bounded ring buffer; ``export_chrome_trace(path)`` writes
  Perfetto-loadable Chrome trace-event JSON.
- **Metrics** (:mod:`autodist_tpu.telemetry.metrics`) — named
  Counter/Gauge/Histogram instruments with a deterministic, wire-encodable
  ``snapshot()``; ``emit_metrics()`` rides the benchmark-logger JSONL sink.
- **Stats plane** — the PS transport's ``stats`` opcode ships a remote
  process's snapshot to whoever asks
  (:meth:`autodist_tpu.parallel.ps_transport.RemotePSWorker.stats`).
- **Cluster trace plane** (:mod:`autodist_tpu.telemetry.cluster`) — span
  rings cross the PS wire (``trace``/``push_trace`` opcodes, ``ping``-based
  clock-offset estimation) and :func:`collect_cluster_trace` merges them
  into ONE clock-aligned Chrome trace with a ``pid`` lane per worker;
  ``tools/tracedump.py`` does the same offline from JSONL ring dumps.

- **Training-health plane** (:mod:`autodist_tpu.telemetry.health`) —
  ``AUTODIST_HEALTH=1`` adds a fused on-device numerics bundle (grad norm,
  update/param ratio, NaN/Inf count) to the existing jitted step plus a
  host-side loss-spike monitor at log boundaries; anomalies become
  ``health.anomaly`` events and the ``AUTODIST_HEALTH_ACTION`` policy
  (warn / record / halt / recover — the last rolls back to the newest
  last-known-good snapshot and resumes, ``parallel/recovery.py``) decides
  the reaction.
- **Flight recorder** (:mod:`autodist_tpu.telemetry.recorder`) — anomaly
  events (watchdog, health, the manual ``record`` wire opcode) capture
  self-contained snapshot dirs (merged cluster trace + metrics/events +
  env manifest) into a bounded latest-K ring; ``tools/adtop.py`` is the
  live console over the ``status`` opcode.
- **Performance attribution** (:mod:`autodist_tpu.telemetry.profiling` +
  :mod:`autodist_tpu.telemetry.costmodel`) — ``AUTODIST_PROFILE=1`` caches
  XLA cost analysis per compiled program signature, decomposes each log
  period into ``train.attr.*`` phase shares, books ``train.mfu`` /
  ``train.membw_util`` roofline gauges, and writes a schema-versioned
  per-run profile (``AUTODIST_PROFILE_DIR``); ``tools/adprof.py`` diffs
  two profiles and the cost model predicts step time from static costs
  plus a calibration fitted from one run.

- **Fleet metrics plane** (:mod:`autodist_tpu.telemetry.history` /
  :mod:`openmetrics` / :mod:`alerts`) — ``AUTODIST_METRICS_DIR`` retains a
  timestamped registry series (in-memory ring + rotation-capped JSONL
  shards), ``AUTODIST_METRICS_PORT`` serves Prometheus-format ``/metrics``
  + ``/healthz`` from any trainer chief / PSServer / InferenceServer
  process, and ``AUTODIST_ALERT_RULES`` evaluates declarative
  threshold/burn-rate/drift rules on every sample (firing books
  ``alert.active.*`` gauges, emits ``alert`` events, triggers the flight
  recorder, and honors ``AUTODIST_ALERT_ACTION``); ``tools/adfleet.py``
  merges ``status`` across N endpoints into one fleet screen.

- **Memory plane** (:mod:`autodist_tpu.telemetry.memplane`) — an
  owner-attributed HBM census (``mem.owned.*`` from weakref claims the
  train loop / paged-KV engine / prefetch producers register), a budget
  with a booked source (measured / env / warned default), the
  ``mem.pressure`` ratio the shipped ``mem_pressure`` alert rule
  thresholds, tuner memory pre-flight (``pruned: oom`` before any compile
  probe), and OOM forensics (a ``memory`` section in every flight-recorder
  manifest: census + per-program ledger + predicted-vs-live peak).

Everything is OFF by default; ``AUTODIST_TELEMETRY=1`` (or
:func:`telemetry.enable`) turns recording on. Disabled-mode instrumentation
costs one attribute check per span (gated in ``bench.py
--telemetry-overhead``); disabled health monitors cost one attribute check
per train step (``bench.py --health-overhead`` gates the enabled side).
"""

import time as _time

# Before the package's own imports (numpy and the planes below are most of a
# second): where ``setup.import_s`` starts, booked at the end of this file.
_IMPORT_T0 = _time.perf_counter()

from autodist_tpu.telemetry import (alerts, history, memplane, openmetrics,
                                    reqtrace)
from autodist_tpu.telemetry.alerts import (AlertEngine, AlertHalt,
                                           AlertRecover, AlertRule)
from autodist_tpu.telemetry.cluster import (collect_cluster_trace,
                                            dump_events_jsonl,
                                            dump_reqtrace_jsonl,
                                            dump_spans_jsonl,
                                            load_events_jsonl,
                                            load_reqtrace_jsonl,
                                            load_trace_jsonl,
                                            local_reqtrace_state,
                                            local_trace_state,
                                            merge_trace_states, ntp_offset,
                                            reqtrace_marks)
from autodist_tpu.telemetry.export import (chrome_trace_events, emit_metrics,
                                           export_chrome_trace,
                                           opt_state_bytes,
                                           sample_device_memory)
from autodist_tpu.telemetry.health import (HealthConfig, HealthHalt,
                                           HealthMonitor, HealthRecover)
from autodist_tpu.telemetry.history import MetricsHistory
from autodist_tpu.telemetry.metrics import (Counter, Gauge, Histogram,
                                            Registry, counter, event, events,
                                            gauge, histogram, merge_histograms,
                                            quantile, registry, snapshot)
from autodist_tpu.telemetry.openmetrics import MetricsExporter
from autodist_tpu.telemetry import costmodel, profiling
from autodist_tpu.telemetry.profiling import (peak_spec, profile_document,
                                              write_profile)
from autodist_tpu.telemetry.recorder import (FlightRecorder, build_manifest,
                                             get_recorder, maybe_record,
                                             set_recorder)
from autodist_tpu.telemetry import phases
from autodist_tpu.telemetry.phases import (format_setup_report, phase,
                                           setup_report)
from autodist_tpu.telemetry.spans import (clear, disable, enable, enabled,
                                          snapshot_spans, span, traced)

__all__ = [
    "span", "traced", "enable", "disable", "enabled", "clear",
    "snapshot_spans",
    "phase", "phases", "setup_report", "format_setup_report",
    "Counter", "Gauge", "Histogram", "Registry",
    "counter", "gauge", "histogram", "registry", "snapshot",
    "event", "events",
    "export_chrome_trace", "chrome_trace_events", "emit_metrics",
    "sample_device_memory", "opt_state_bytes",
    "collect_cluster_trace", "local_trace_state", "merge_trace_states",
    "dump_spans_jsonl", "load_trace_jsonl", "ntp_offset",
    "dump_events_jsonl", "load_events_jsonl",
    "reqtrace", "local_reqtrace_state", "reqtrace_marks",
    "dump_reqtrace_jsonl", "load_reqtrace_jsonl",
    "HealthConfig", "HealthHalt", "HealthMonitor", "HealthRecover",
    "FlightRecorder", "set_recorder", "get_recorder", "maybe_record",
    "build_manifest",
    "profiling", "costmodel", "peak_spec", "profile_document",
    "write_profile",
    "alerts", "history", "memplane", "openmetrics",
    "AlertEngine", "AlertHalt", "AlertRecover", "AlertRule",
    "MetricsHistory",
    "MetricsExporter", "quantile", "merge_histograms",
]


# The set-up ledger's first entry: this package's own import.
phases.package_imported(_IMPORT_T0)
