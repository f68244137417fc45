"""Telemetry exporters: Chrome trace-event JSON and benchmark-logger JSONL.

Two sinks for the two telemetry planes:

- :func:`export_chrome_trace` writes the span ring buffer as a Chrome
  trace-event file (the ``{"traceEvents": [...]}`` object form) that loads in
  ui.perfetto.dev or ``chrome://tracing``: the host timeline on its own, or
  per worker for the cluster merge. For host spans beside a device trace no
  export is needed: an enabled span is a ``jax.profiler.TraceAnnotation``, so
  a profiler session's own trace holds it (docs/usage/observability.md).
- :func:`emit_metrics` writes the metrics-registry snapshot as JSONL metric
  rows through the existing :mod:`autodist_tpu.utils.benchmark_logger` file
  sink (one ``metric.log`` line per instrument), so registry metrics land in
  the same file scrapers already parse.
"""

import functools
import json
import math
from typing import Dict, Optional, Tuple

from autodist_tpu.telemetry import metrics as _metrics
from autodist_tpu.telemetry import spans as _spans
from autodist_tpu.utils import logging

__all__ = ["export_chrome_trace", "emit_metrics", "sample_device_memory",
           "opt_state_bytes", "device_bytes", "leaf_device_bytes"]


@functools.lru_cache(maxsize=8192)
def _shard_bytes(sharding, shape, dtype) -> Tuple[int, Tuple[int, ...]]:
    """(bytes of one shard, the local devices that hold one) of an array of
    ``shape`` and ``dtype`` under ``sharding``: from the shard shape, so no
    shard is materialized, and remembered, so a leaf met again at the next log
    boundary costs a lookup."""
    return (dtype.itemsize * math.prod(sharding.shard_shape(shape)),
            tuple(dev.id for dev in sharding.addressable_devices))


def leaf_device_bytes(leaf) -> Optional[Tuple[int, Tuple[int, ...]]]:
    """What each local device holds of one ``jax.Array`` (bytes of a shard, the
    ids of the devices with one: a replicated array its full size on every
    device, one sharded n ways ``1/n``); ``(0, ())`` for a donated array, whose
    bytes are its heir's; None for anything that is not a device array."""
    import jax
    if not isinstance(leaf, jax.Array):
        return None
    try:
        if leaf.is_deleted():
            return 0, ()
        return _shard_bytes(leaf.sharding, leaf.shape, leaf.dtype)
    except (RuntimeError, ValueError, TypeError, AttributeError):
        return None                       # exotic backend or sharding


def device_bytes(tree) -> Tuple[Dict[int, int], int]:
    """The bytes each local device holds of ``tree``'s ``jax.Array`` leaves
    (``{device id: bytes}``, :func:`leaf_device_bytes`) and the bytes of its
    host (numpy) leaves: cheap enough to count every array of a process at a
    log boundary."""
    import jax
    per_dev: Dict[int, int] = {}
    host = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        held = leaf_device_bytes(leaf)
        if held is None:
            host += int(getattr(leaf, "nbytes", 0) or 0)
            continue
        for dev in held[1]:
            per_dev[dev] = per_dev.get(dev, 0) + held[0]
    return per_dev, host


def opt_state_bytes(tree) -> int:
    """Per-device resident bytes of a tree (the optimizer state, the
    parameters, a whole ``TrainState``, every live array): the most any local
    device holds of it (:func:`device_bytes`), host leaves counted once as
    chief-resident. For an optimizer state this is exactly the number
    weight-update sharding divides (`bench.py --zero` gates the ratio, and
    ``train()`` samples it as the ``train.opt_state_bytes`` gauge at log
    boundaries); the memory plane's census (``memplane.tag``) and the HBM
    account's ``train.hbm.state_bytes`` count with it too, so every one of
    them is a chip's bytes on any mesh."""
    per_dev, host = device_bytes(tree)
    return max(per_dev.values(), default=0) + host


def chrome_trace_events(pid: Optional[int] = None,
                        clock_offset_ns: int = 0) -> list:
    """The recorded spans as a list of Chrome trace-event dicts: one ``"M"``
    thread_name metadata event per recorded thread, then one ``"X"``
    (complete) event per span with microsecond ``ts``/``dur`` relative to the
    ring's epoch.

    ``pid`` overrides the lane id (Chrome groups events by pid, so each
    worker exporting under its own lane id merges collision-free) and
    ``clock_offset_ns`` is ADDED to every span timestamp before the µs
    conversion — together they let per-worker exports land on one shared
    timeline with no post-hoc JSON rewriting (the cluster trace plane's
    :mod:`autodist_tpu.telemetry.cluster` computes the offsets)."""
    real_pid, epoch_ns, recorded, thread_names, _, _ = \
        _spans._export_state()
    if pid is None:
        pid = real_pid
    events = []
    for tid, name in sorted(thread_names.items()):
        events.append({"ph": "M", "name": "thread_name", "pid": pid,
                       "tid": tid, "args": {"name": name}})
    for name, tid, t0_ns, dur_ns, args in recorded:
        events.append({
            "name": name,
            "ph": "X",
            "cat": "host",
            # trace-event ts unit: usec
            "ts": (t0_ns - epoch_ns + clock_offset_ns) / 1e3,
            "dur": dur_ns / 1e3,
            "pid": pid,
            "tid": tid,
            "args": args or {},
        })
    return events


def export_chrome_trace(path: str, pid: Optional[int] = None,
                        clock_offset_ns: int = 0) -> str:
    """Write the span ring buffer to ``path`` as Chrome trace-event JSON;
    returns ``path``. Safe to call repeatedly (each call snapshots the ring);
    an empty ring writes a valid empty trace. ``pid`` and ``clock_offset_ns``
    relabel/rebase the lane for merged multi-worker timelines (see
    :func:`chrome_trace_events`)."""
    doc = {"traceEvents": chrome_trace_events(pid=pid,
                                              clock_offset_ns=clock_offset_ns),
           "displayTimeUnit": "ms"}
    with open(path, "w") as f:
        json.dump(doc, f)
    logging.info("Wrote %d host span event(s) to %s",
                 len(doc["traceEvents"]), path)
    return path


def sample_device_memory(opt_state=None, state=None) -> int:
    """Sample live-buffer and device-memory gauges into the registry; returns
    the number of gauges written. Every byte count is what ONE chip holds, the
    fullest (:func:`device_bytes`), the unit of the allocator's readings.

    Gauges: ``device.live_buffers`` / ``device.live_bytes`` (count of
    ``jax.live_arrays()`` and the bytes the fullest device holds of them — a
    leak shows as monotonic growth across log boundaries) and, where the
    backend reports allocator stats (TPU/GPU; CPU returns none), per-device
    ``device.mem.bytes_in_use.d<id>`` / ``device.mem.bytes_limit.d<id>``.
    With ``opt_state``, additionally writes ``train.opt_state_bytes`` — the
    per-device optimizer-state footprint (:func:`opt_state_bytes`), the gauge
    ZeRO weight-update sharding divides by the data-parallel size.

    The memory plane's attribution pass rides the same sample: the live bytes
    are decomposed over the :mod:`~autodist_tpu.telemetry.memplane` tag
    registry into
    ``mem.owned.{params,opt_state,kv_pages,prefetch,snapshots,other}``
    gauges (``other`` = live minus claimed, the leak-hunting residual,
    clamped at zero) plus the ``mem.pressure`` ratio the shipped
    ``mem_pressure`` alert rule thresholds — so owners and pressure flow into
    history shards, OpenMetrics, and adfleet with no extra sampling path.

    With ``state`` (``train()``'s ``TrainState`` at a log boundary) ONE walk
    of its leaves re-points the census's ``params`` / ``opt_state`` claims at
    this boundary's arrays (the step donates its inputs, so the last
    boundary's claims are dead weakrefs by now), gives ``train.opt_state_bytes``
    and the HBM account's ``train.hbm.state_bytes``, and the allocator
    readings below book the account's other boundary gauges
    (``memplane.book_hbm_boundary``: ``train.hbm.*``).
    Called by ``train()`` at log boundaries when telemetry is enabled; a
    diagnostics sampler must never break training, so backend hiccups are
    swallowed at debug level."""
    import jax
    from autodist_tpu.telemetry import memplane as _memplane
    wrote = 0
    chip_live = 0
    held = None
    try:
        if state is not None:
            held, _ = _memplane.tag("params", state.params)
            opt_dev, opt_host = _memplane.tag("opt_state", state.opt_state)
            opt_bytes = max(opt_dev.values(), default=0) + opt_host
            rest, _ = device_bytes((state.step, state.ef_state))
            for part in (opt_dev, rest):
                for dev, nb in part.items():
                    held[dev] = held.get(dev, 0) + nb
        else:
            opt_bytes = None if opt_state is None \
                else opt_state_bytes(opt_state)
        if opt_bytes is not None:
            _metrics.gauge("train.opt_state_bytes").set(opt_bytes)
            wrote += 1
    except (RuntimeError, ValueError, TypeError, AttributeError) as e:
        logging.debug("opt-state byte sampling unavailable: %s", e)
    try:
        live = jax.live_arrays()
        chip_live = opt_state_bytes(live)
        _metrics.gauge("device.live_buffers").set(len(live))
        _metrics.gauge("device.live_bytes").set(chip_live)
        wrote += 2
    except (RuntimeError, ValueError, TypeError, AttributeError) as e:
        logging.debug("live-array sampling unavailable: %s", e)
    try:
        for owner, nbytes in _memplane.attribute(chip_live).items():
            _metrics.gauge(f"mem.owned.{owner}").set(int(nbytes))
            wrote += 1
        _memplane.current_pressure(max_age_s=0.0)   # books mem.pressure
        wrote += 1
    except Exception as e:  # noqa: BLE001 — attribution is best-effort
        logging.debug("memory attribution unavailable: %s", e)
    try:
        devices = jax.local_devices()
    except RuntimeError as e:  # backend not initialized yet
        logging.debug("device-memory sampling unavailable: %s", e)
        return wrote
    stats = {d.id: _memplane.device_stats(d) for d in devices}
    stats = {dev: s for dev, s in stats.items() if s}
    for dev, reading in stats.items():
        for key in ("bytes_in_use", "bytes_limit"):
            value = reading.get(key)
            if value is not None:
                _metrics.gauge(f"device.mem.{key}.d{dev}").set(int(value))
                wrote += 1
    if held is not None:
        try:
            wrote += _memplane.book_hbm_boundary(stats, held)
        except Exception as e:  # noqa: BLE001 — the account is best-effort
            logging.debug("HBM account unavailable: %s", e)
    return wrote


_EMIT_LOGGER = None


def emit_metrics(global_step: Optional[int] = None, logger=None,
                 require_file_sink: bool = True) -> int:
    """Emit the registry snapshot through the benchmark-logger sink; returns
    the number of rows written.

    With ``require_file_sink`` (the default) emission is a no-op unless
    ``AUTODIST_BENCHMARK_LOG_DIR`` selects the JSONL file sink — the train
    loop calls this every log period, and mirroring a whole snapshot into the
    console logger each period would be noise, not observability. Histograms
    emit their ``count`` as the value with the full bucket dict in
    ``extras``."""
    global _EMIT_LOGGER
    from autodist_tpu.utils import benchmark_logger
    if logger is None:
        if _EMIT_LOGGER is None:
            candidate = benchmark_logger.get_benchmark_logger()
            if isinstance(candidate, benchmark_logger.BenchmarkFileLogger):
                # Cache ONLY the file sink (one open handle per process). A
                # base-logger result is re-evaluated next call, so setting
                # AUTODIST_BENCHMARK_LOG_DIR later in the process still
                # switches emission on instead of being frozen out forever.
                _EMIT_LOGGER = candidate
            elif require_file_sink:
                return 0
            logger = _EMIT_LOGGER if _EMIT_LOGGER is not None else candidate
        else:
            logger = _EMIT_LOGGER
    return logger.log_metrics(_metrics.snapshot(), global_step=global_step)
