"""Performance attribution: static program costs, phase shares, roofline gauges.

The telemetry stack records WHAT happened (spans, metrics, anomalies); this
module answers WHY A STEP IS SLOW, in three layers over the same substrate:

- **Static cost extraction** — at the runner's compile-probe site (the shape-
  signature dispatch in ``runner.py``), every first-of-its-signature program
  contributes ``lowered.compile().cost_analysis()`` (flops, bytes accessed,
  output bytes) to a per-signature :class:`ProgramCost` cache; later
  dispatches of the same signature only bump its dispatch count. Where the
  backend reports nothing (pallas-dominated programs), an analytic estimate
  installed via :func:`set_analytic_flops` (``utils/flops.py``'s counts)
  stands in, marked ``source="analytic"``.
- **Phase attribution + roofline gauges** — :func:`observe_period` decomposes
  each train() log period's wall time into ``train.attr.{data_wait,host,comm,
  compute,readback}`` share gauges by joining the period's span durations
  (``spans._export_columns``) against the host timeline, and books
  ``train.mfu`` / ``train.membw_util`` — achieved flops/s and bytes/s over
  the :func:`peak_spec` hardware peaks — from the period's dispatched program
  costs. ``compute`` is the residual: wall time the host spent neither
  producing data, dispatching, on the wire, nor syncing — i.e. parked behind
  the device. Shares always sum to 1.0 (test-pinned).
- **Profile store** — :func:`write_profile` emits one schema-versioned JSON
  per run (program costs, per-period attribution + MFU series, weighted
  summary, env manifest via the flight recorder's manifest helper);
  ``tools/adprof.py`` summarizes and DIFFS two profiles, naming the regressed
  phase, and :mod:`autodist_tpu.telemetry.costmodel` calibrates a step-time
  predictor from one — the interface ROADMAP item 3's strategy search calls.

Cost contract: everything here keys off :func:`active` — profiling rides the
span plane, so :func:`enable` also enables spans. With profiling off and
telemetry on, dispatch counting is one dict increment per dispatch; with
both off, the hot paths pay nothing new (``bench.py --attr-overhead`` gates
the enabled side at <=2% of a host-bound step).
"""

import json
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from autodist_tpu import const
from autodist_tpu.telemetry import metrics as _metrics
from autodist_tpu.telemetry import spans as _spans
from autodist_tpu.utils import logging
from autodist_tpu.testing.sanitizer import san_lock

__all__ = ["PeakSpec", "peak_spec", "ProgramCost", "enable", "disable",
           "active", "reset", "note_dispatch", "record_program_cost",
           "program_costs", "set_analytic_flops", "set_applied_plan",
           "applied_plan", "observe_period",
           "format_attr_line", "format_shares", "attribution_periods",
           "profile_document",
           "write_profile", "maybe_write_profile", "PROFILE_SCHEMA",
           "PROFILE_SCHEMA_VERSION", "ATTR_PHASES"]

# Profile JSON identity, pinned by tests and read back by tools/adprof.py and
# telemetry/costmodel.py. Bump the version on any breaking key change.
PROFILE_SCHEMA = "autodist-profile"
PROFILE_SCHEMA_VERSION = 1

# The attribution phases, in the order log lines and adprof render them.
ATTR_PHASES = ("compute", "comm", "host", "data_wait", "readback")

# bf16 peak FLOP/s per chip by device_kind prefix (public spec sheets) —
# migrated here from utils/flops.py so FLOPs and bandwidth peaks live in ONE
# peak-spec table (flops.device_peak_flops delegates back to peak_spec()).
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 197e12,
    "TPU v4": 275e12,
    "TPU v6 lite": 918e12,   # v6e (Trillium)
    "TPU v6e": 918e12,
}

# HBM bandwidth per chip, bytes/s (public spec sheets), same prefix keying.
PEAK_HBM_BYTES = {
    "TPU v5 lite": 819e9,    # v5e: 819 GB/s
    "TPU v5e": 819e9,
    "TPU v5p": 2765e9,
    "TPU v5": 819e9,
    "TPU v4": 1228e9,
    "TPU v6 lite": 1640e9,   # v6e
    "TPU v6e": 1640e9,
}


@dataclass(frozen=True)
class PeakSpec:
    """Per-device hardware peaks the roofline gauges divide by. ``None``
    means unknown (e.g. CPU without an override) — dependent gauges are
    simply not booked then, never guessed."""

    flops_per_s: Optional[float]
    membw_bytes_per_s: Optional[float]
    source: str   # "env" | "device:<kind>" | "unknown" | "unknown:<kind>"

    def to_dict(self) -> Dict[str, Any]:
        return {"flops_per_s": self.flops_per_s,
                "membw_bytes_per_s": self.membw_bytes_per_s,
                "source": self.source}


_WARNED_PEAKS = set()


def _parse_peak(raw: str, flag: str) -> Optional[float]:
    """A peak override as float, or None when unset OR malformed — peaks
    must never break a run (observe_period calls this at every training log
    boundary), so a typo'd ``AUTODIST_PEAK_FLOPS=197T`` warns once and
    degrades to unknown instead of raising."""
    if not raw:
        return None
    try:
        return float(raw)
    except ValueError:
        if flag not in _WARNED_PEAKS:
            _WARNED_PEAKS.add(flag)
            logging.warning("%s=%r is not a number; ignoring the override "
                            "(use plain floats like 197e12)", flag, raw)
        return None


def peak_spec(device=None) -> PeakSpec:
    """The shared peak-spec helper: per-device peak FLOP/s and HBM bytes/s.

    ``AUTODIST_PEAK_FLOPS`` / ``AUTODIST_PEAK_MEMBW`` override either side
    (new hardware, calibrated peaks); otherwise both come from the device
    kind's spec-sheet tables. CPU yields ``None`` sides (MFU against a
    meaningless peak would be noise) under source ``"unknown"``; an
    accelerator kind missing from a table yields ``"unknown:<kind>"``, never
    ``"device:<kind>"`` with an empty peak."""
    flops_env = str(const.ENV.AUTODIST_PEAK_FLOPS.val)
    membw_env = str(const.ENV.AUTODIST_PEAK_MEMBW.val)
    flops = _parse_peak(flops_env, "AUTODIST_PEAK_FLOPS")
    membw = _parse_peak(membw_env, "AUTODIST_PEAK_MEMBW")
    if flops is None:
        flops_env = ""   # a rejected override falls through to the tables
    if membw is None:
        membw_env = ""
    if flops is not None and membw is not None:
        return PeakSpec(flops, membw, "env")
    kind = ""
    if flops is None or membw is None:
        try:
            import jax
            device = device or jax.devices()[0]
            if device.platform != "cpu":
                kind = getattr(device, "device_kind", "") or ""
        except Exception:  # noqa: BLE001 — peaks must never break a run
            kind = ""
    for prefix, peak in PEAK_BF16_FLOPS.items():
        if kind.startswith(prefix):
            flops = peak if flops is None else flops
            break
    for prefix, peak in PEAK_HBM_BYTES.items():
        if kind.startswith(prefix):
            membw = peak if membw is None else membw
            break
    if flops_env or membw_env:
        source = "env"
    elif kind and flops is not None and membw is not None:
        source = f"device:{kind}"
    elif kind:
        # An accelerator the tables do not know: say so in the source, so a
        # measurement path can refuse it instead of printing "mfu": null.
        source = f"unknown:{kind}"
    else:
        source = "unknown"
    return PeakSpec(flops, membw, source)


@dataclass
class ProgramCost:
    """One compiled program's static cost record, keyed by the runner's
    shape-signature digest (the crc32 the ``jit.compile`` span carries).
    ``flops``/``bytes_accessed`` are PER DISPATCH of the program — a fused
    ``steps=K`` block program already contains its K scanned steps, so
    per-step numbers divide by ``steps``."""

    sig: str
    kind: str                       # "step" | "many" | caller-defined
    steps: int = 1                  # train steps one dispatch advances
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    output_bytes: Optional[float] = None
    compile_s: Optional[float] = None
    dispatches: int = 0
    source: Optional[str] = None    # "xla" | "analytic" | None (unknown)
    # The memory ledger: XLA's full memory_analysis() per program — the
    # bytes a dispatch pins while it runs (arguments + outputs + temps +
    # code), UNscaled by steps (unlike flops, a K-step block's working set
    # does not multiply). ``temp_bytes`` is the term the cost model adds to
    # resident state for its peak-HBM estimate.
    # ``alias_bytes`` is the part of the outputs that lives in donated
    # arguments (the new state in the old one's buffers): arguments + temps +
    # outputs - alias is what a dispatch needs on a device.
    argument_bytes: Optional[int] = None
    temp_bytes: Optional[int] = None
    alias_bytes: Optional[int] = None
    generated_code_bytes: Optional[int] = None

    def to_dict(self) -> Dict[str, Any]:
        return {"kind": self.kind, "steps": self.steps, "flops": self.flops,
                "bytes_accessed": self.bytes_accessed,
                "output_bytes": self.output_bytes,
                "compile_s": self.compile_s, "dispatches": self.dispatches,
                "source": self.source,
                "argument_bytes": self.argument_bytes,
                "temp_bytes": self.temp_bytes,
                "alias_bytes": self.alias_bytes,
                "generated_code_bytes": self.generated_code_bytes}


class _State:
    """Process-global profiling state; one lock covers the cost cache and the
    period bookkeeping (boundary-rate access only — never per dispatch
    beyond one dict increment)."""

    def __init__(self):
        self.enabled = False
        self.lock = san_lock()
        self.costs: Dict[str, ProgramCost] = {}
        self.analytic_flops_per_step: Optional[float] = None
        self.periods: List[Dict[str, Any]] = []
        self.period_start_ns: Optional[int] = None
        self.last_dispatches: Dict[str, int] = {}
        # The execution plan this process applied (the autotuner's record:
        # cache key + knobs + predicted vs measured) — attached to profile
        # JSONs and flight-recorder manifests so a snapshot or adprof diff
        # names which plan a run was executing. Survives reset(): it
        # describes the session, not an attribution period.
        self.applied_plan: Optional[Dict[str, Any]] = None


_STATE = _State()
_MAX_PERIODS = 4096   # ~4k log boundaries per run retained in a profile


def enable():
    """Turn the attribution plane on. Profiling joins span durations, so this
    also enables span recording (the reverse is not true: telemetry alone
    never pays for cost extraction)."""
    _STATE.enabled = True
    _spans.enable()
    with _STATE.lock:
        if _STATE.period_start_ns is None:
            _STATE.period_start_ns = time.perf_counter_ns()
            # Baseline the dispatch counters at the window open: telemetry-
            # only runs count dispatches too (note_dispatch), and a mid-run
            # enable() must not charge the whole prior run's dispatches to
            # its first period.
            _STATE.last_dispatches = {sig: rec.dispatches
                                      for sig, rec in _STATE.costs.items()}


def disable():
    _STATE.enabled = False
    with _STATE.lock:
        # Close the attribution clock: without this, the first
        # observe_period after a re-enable would charge the whole disabled
        # stretch (no spans recorded there, so it lands in "compute") to
        # its period — exactly what the interleaved attr-overhead bench
        # rounds would hit.
        _STATE.period_start_ns = None


def active() -> bool:
    return _STATE.enabled


def reset():
    """Drop every cost record and attribution period (tests; production
    profiling state lives for the process)."""
    with _STATE.lock:
        _STATE.costs.clear()
        _STATE.periods.clear()
        _STATE.last_dispatches.clear()
        _STATE.analytic_flops_per_step = None
        _STATE.period_start_ns = (time.perf_counter_ns()
                                  if _STATE.enabled else None)


def set_applied_plan(plan: Optional[Dict[str, Any]]):
    """Record the execution plan this process is running (the autotuner's
    ``TunedPlan.to_dict()`` + name). Rides every subsequently-written
    profile document (``"plan"`` key) and flight-recorder manifest, so
    diagnostics name the plan a run was executing. ``None`` clears."""
    with _STATE.lock:
        _STATE.applied_plan = dict(plan) if plan else None


def applied_plan() -> Optional[Dict[str, Any]]:
    with _STATE.lock:
        return dict(_STATE.applied_plan) if _STATE.applied_plan else None


def set_analytic_flops(flops_per_step: Optional[float]):
    """Install the analytic per-step FLOPs fallback (``utils/flops.py``'s
    counts) used when a compiled program reports no cost analysis — the
    pallas-kernel case, where XLA sees an opaque custom call."""
    with _STATE.lock:
        _STATE.analytic_flops_per_step = flops_per_step


def note_dispatch(sig: str, kind: str, steps: int = 1):
    """Count one dispatch of signature ``sig`` (get-or-create its record).
    Called by the runner for EVERY compiled-program dispatch while telemetry
    is enabled — one dict increment, so it is cheap enough to ride the
    existing signature computation."""
    with _STATE.lock:
        rec = _STATE.costs.get(sig)
        if rec is None:
            rec = _STATE.costs[sig] = ProgramCost(sig=sig, kind=kind,
                                                  steps=int(steps))
        rec.dispatches += 1


MEMORY_FIELDS = ("argument_bytes", "output_bytes", "temp_bytes",
                 "alias_bytes", "generated_code_bytes")


def _set_memory(rec: ProgramCost, memory: Optional[Dict[str, Any]]):
    for field in MEMORY_FIELDS:
        if memory and memory.get(field) is not None:
            setattr(rec, field, int(memory[field]))


def record_program_memory(sig: str, memory: Optional[Dict[str, Any]]):
    """Attach a compiled program's ``memory_analysis()`` (the runner's
    ``MEMORY_FIELDS`` dict) to the record its dispatch count made: the memory
    ledger of a telemetry-enabled run whose attribution plane is off."""
    with _STATE.lock:
        rec = _STATE.costs.get(sig)
        if rec is not None:
            _set_memory(rec, memory)


def record_program_cost(sig: str, kind: str, steps: int,
                        cost: Optional[Dict[str, float]],
                        compile_s: Optional[float] = None) -> ProgramCost:
    """Attach a compiled program's static costs to its signature record
    (creating it if the dispatch count never touched it). ``cost`` is the
    runner-extracted ``{"flops", "bytes_accessed", "output_bytes"}`` dict
    (plus the :data:`MEMORY_FIELDS` memory ledger), or None when the backend
    reported nothing — the analytic fallback (scaled by ``steps``) stands in
    then."""
    with _STATE.lock:
        rec = _STATE.costs.get(sig)
        if rec is None:
            rec = _STATE.costs[sig] = ProgramCost(sig=sig, kind=kind,
                                                  steps=int(steps))
        rec.kind = kind
        rec.steps = int(steps)
        if compile_s is not None:
            rec.compile_s = float(compile_s)
        # The memory ledger rides independently of the flops report: a
        # pallas-opaque program can still name its working set.
        _set_memory(rec, cost)
        analytic = None
        if _STATE.analytic_flops_per_step is not None:
            analytic = float(_STATE.analytic_flops_per_step) * int(steps)
        if cost and cost.get("flops"):
            rec.flops = float(cost["flops"])
            rec.bytes_accessed = cost.get("bytes_accessed")
            rec.output_bytes = cost.get("output_bytes")
            rec.source = "xla"
            # Partially-pallas programs report nonzero-but-short flops (XLA
            # counts its own ops, not the custom call's — the flagship's
            # fused vocab head is the dominant term it misses). Each
            # accounting is a LOWER bound on what executes, so take
            # whichever sees more.
            if analytic is not None and analytic > rec.flops:
                rec.flops = analytic
                rec.source = "analytic"
        elif analytic is not None:
            rec.flops = analytic
            rec.source = "analytic"
        return rec


def program_costs() -> Dict[str, ProgramCost]:
    """A point-in-time copy of the per-signature cost cache."""
    with _STATE.lock:
        return dict(_STATE.costs)


# ------------------------------------------------------------- attribution

# Span-name -> phase classification. ``train.dispatch`` is the gross host
# cost of one step's feed/dispatch work (it wraps shard_batch + the enqueue
# + any synchronous PS exchange); ``ps.*`` spans nested inside it are pulled
# out as ``comm``, and the unrolled loop's ``runner.shard_block`` spans —
# recorded in gather(), OUTSIDE train.dispatch — are added back in (block
# stacking + h->d transfer is host work even when it overlaps the device;
# the attribution is a host-timeline decomposition). Outside train() (a
# bare runner loop) the dispatch spans themselves stand in for the host
# phase.
_HOST_SPANS = ("train.dispatch",)
_HOST_SIBLING_SPANS = ("runner.shard_block",)
_HOST_FALLBACK_SPANS = ("runner.run.dispatch", "runner.run_many.dispatch",
                        "runner.shard_batch", "runner.shard_block",
                        "jit.compile")


def _period_span_seconds(since_ns: int) -> Dict[str, float]:
    """Sum span durations since ``since_ns`` into phase buckets (seconds)."""
    (_, _, names, _, name_idx, _, t0s, durs, _,
     _, _, _) = _spans._export_columns(since_ns)
    by_name: Dict[str, float] = {}
    for n, dur in zip(name_idx, durs):
        name = names[n]
        by_name[name] = by_name.get(name, 0.0) + dur
    data_wait = by_name.get("train.data_wait", 0.0)
    readback = by_name.get("train.readback_wait", 0.0)
    comm = sum(v for k, v in by_name.items() if k.startswith("ps."))
    host = sum(by_name.get(k, 0.0) for k in _HOST_SPANS)
    if host:
        # ps.* exchanges run nested inside train.dispatch — pull them out so
        # comm is not double-counted as host; gather()'s shard_block spans
        # are train.dispatch SIBLINGS, so they add.
        host = max(0.0, host - comm) \
            + sum(by_name.get(k, 0.0) for k in _HOST_SIBLING_SPANS)
    else:
        host = sum(by_name.get(k, 0.0) for k in _HOST_FALLBACK_SPANS)
    return {"data_wait": data_wait / 1e9, "host": host / 1e9,
            "comm": comm / 1e9, "readback": readback / 1e9}


def observe_period(step: Optional[int] = None,
                   require_steps: bool = False) -> Optional[Dict[str, Any]]:
    """Close one attribution period at a train-loop log boundary.

    Joins the period's span durations against its dispatched program costs
    and books the gauges: ``train.attr.<phase>`` (fractions of period wall
    time, summing to 1.0 — ``compute`` is the unexplained residual, i.e. the
    host parked behind the device), ``train.mfu`` / ``train.membw_util``
    (achieved over :func:`peak_spec` peaks, only when both sides are known)
    and ``train.flops_per_s``. Returns the period record (appended to the
    profile's series), or None when profiling is off or the period is
    degenerate (zero wall time).

    ``require_steps=True`` (the end-of-run flush) drops a period that saw
    NO dispatches — a run whose last boundary just closed would otherwise
    append a step-less tail (checkpoint/teardown wall time) that distorts
    the period-weighted summary."""
    if not _STATE.enabled:
        return None
    now_ns = time.perf_counter_ns()
    with _STATE.lock:
        start_ns = _STATE.period_start_ns
        _STATE.period_start_ns = now_ns
        if start_ns is None or now_ns <= start_ns:
            return None
        # Dispatch deltas since the last boundary, joined against costs.
        flops = bytes_acc = 0.0
        steps = dispatches = 0
        flops_known = True
        for sig, rec in _STATE.costs.items():
            delta = rec.dispatches - _STATE.last_dispatches.get(sig, 0)
            if delta <= 0:
                continue
            _STATE.last_dispatches[sig] = rec.dispatches
            dispatches += delta
            steps += delta * rec.steps
            if rec.flops is not None:
                flops += delta * rec.flops
                if rec.bytes_accessed is not None:
                    bytes_acc += delta * rec.bytes_accessed
            else:
                flops_known = False
    if require_steps and steps == 0:
        return None
    period_s = (now_ns - start_ns) / 1e9
    measured = _period_span_seconds(start_ns)
    # Residual = wall time not explained by any instrumented host phase: the
    # loop parked behind the device (or uninstrumented host work). Clamped
    # at 0 when overlapped background threads (the PS prefetch socket) make
    # measured phase time exceed wall time; normalizing by the parts' sum
    # keeps the shares a distribution either way.
    residual = max(0.0, period_s - sum(measured.values()))
    parts = dict(measured, compute=residual)
    total = sum(parts.values())
    if total <= 0:
        return None
    shares = {k: parts[k] / total for k in ATTR_PHASES}
    peaks = peak_spec()
    flops_per_s = (flops / period_s) if flops else None
    bytes_per_s = (bytes_acc / period_s) if bytes_acc else None
    mfu = (flops_per_s / peaks.flops_per_s
           if flops_per_s and peaks.flops_per_s else None)
    membw = (bytes_per_s / peaks.membw_bytes_per_s
             if bytes_per_s and peaks.membw_bytes_per_s else None)
    record: Dict[str, Any] = {
        "step": step,
        "period_s": round(period_s, 6),
        "steps": steps,
        "dispatches": dispatches,
        "steps_per_s": round(steps / period_s, 4) if steps else None,
        "shares": {k: round(v, 4) for k, v in shares.items()},
        "flops_per_s": flops_per_s,
        "bytes_per_s": bytes_per_s,
        "flops_known": flops_known,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "membw_util": round(membw, 4) if membw is not None else None,
    }
    for phase in ATTR_PHASES:
        _metrics.gauge(f"train.attr.{phase}").set(record["shares"][phase])
    if flops_per_s is not None:
        _metrics.gauge("train.flops_per_s").set(flops_per_s)
    if mfu is not None:
        _metrics.gauge("train.mfu").set(record["mfu"])
    if membw is not None:
        _metrics.gauge("train.membw_util").set(record["membw_util"])
    with _STATE.lock:
        _STATE.periods.append(record)
        if len(_STATE.periods) > _MAX_PERIODS:
            del _STATE.periods[0]
    return record


def attribution_periods() -> List[Dict[str, Any]]:
    """A copy of the recorded per-period attribution series."""
    with _STATE.lock:
        return list(_STATE.periods)


_SHARE_ABBREV = {"compute": "comp", "comm": "comm", "host": "host",
                 "data_wait": "data", "readback": "rb"}


def format_shares(shares: Dict[str, float]) -> str:
    """``comp .61 comm .05 host .22 data .07 rb .05`` — the ONE compact
    share rendering, shared by the ``train:`` log-line suffix and adtop's
    ``perf`` line so the two can never drift. Phases absent from ``shares``
    are skipped (adtop renders whatever gauges the run booked)."""
    return " ".join(
        f"{_SHARE_ABBREV[k]} {shares[k]:.2f}".replace(" 0.", " .")
        for k in ATTR_PHASES if k in shares)


def format_attr_line(record: Optional[Dict[str, Any]]) -> str:
    """The compact ``train:`` log-line suffix for one period record:
    ``mfu 28.3% | comp .61 comm .05 host .22 data .07 rb .05`` (phases
    abbreviated, mfu omitted when unknown)."""
    if not record:
        return ""
    mfu = record.get("mfu")
    head = f"mfu {100.0 * mfu:.1f}% | " if mfu is not None else ""
    return f" | {head}{format_shares(record['shares'])}"


# ------------------------------------------------------------ profile store

def _summary(periods: List[Dict[str, Any]],
             costs: Dict[str, ProgramCost]) -> Dict[str, Any]:
    """Period_s-weighted aggregate of the attribution series plus per-step
    cost averages — the numbers adprof diffs and costmodel calibrates on."""
    total_s = sum(p["period_s"] for p in periods)
    total_steps = sum(p["steps"] for p in periods)
    total_disp = sum(p["dispatches"] for p in periods)
    out: Dict[str, Any] = {
        "wall_s": round(total_s, 6),
        "steps": total_steps,
        "dispatches": total_disp,
        "steps_per_s": round(total_steps / total_s, 4)
        if total_s and total_steps else None,
        "step_s": round(total_s / total_steps, 6)
        if total_steps else None,
    }
    if total_s:
        shares = {k: sum(p["shares"][k] * p["period_s"] for p in periods)
                  / total_s for k in ATTR_PHASES}
        out["shares"] = {k: round(v, 4) for k, v in shares.items()}
        mfus = [(p["mfu"], p["period_s"]) for p in periods
                if p.get("mfu") is not None]
        if mfus:
            out["mfu"] = round(sum(m * w for m, w in mfus)
                               / sum(w for _, w in mfus), 4)
        bw = [(p["membw_util"], p["period_s"]) for p in periods
              if p.get("membw_util") is not None]
        if bw:
            out["membw_util"] = round(sum(m * w for m, w in bw)
                                      / sum(w for _, w in bw), 4)
    flops = sum((r.flops or 0.0) * r.dispatches for r in costs.values())
    bytes_acc = sum((r.bytes_accessed or 0.0) * r.dispatches
                    for r in costs.values())
    run_steps = sum(r.steps * r.dispatches for r in costs.values())
    if run_steps:
        out["flops_per_step"] = flops / run_steps if flops else None
        out["bytes_per_step"] = bytes_acc / run_steps if bytes_acc else None
    if total_disp and total_steps and out.get("step_s") and out.get("shares"):
        # Host seconds per dispatch: what the cost model charges each
        # program launch (dispatch amortization is why unroll=K wins).
        out["host_s_per_dispatch"] = round(
            out["shares"]["host"] * out["step_s"] * total_steps / total_disp,
            9)
    return out


def profile_document(extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The in-memory profile: schema header, env manifest (the flight
    recorder's helper), hardware peaks, per-signature program costs, the
    attribution series, and the weighted summary."""
    from autodist_tpu.telemetry import recorder as _recorder
    periods = attribution_periods()
    costs = program_costs()
    doc: Dict[str, Any] = {
        "schema": PROFILE_SCHEMA,
        "schema_version": PROFILE_SCHEMA_VERSION,
        "manifest": _recorder.build_manifest("profile"),
        "peaks": peak_spec().to_dict(),
        "programs": {sig: rec.to_dict() for sig, rec in sorted(costs.items())},
        "periods": periods,
        "summary": _summary(periods, costs),
    }
    plan = applied_plan()
    if plan:
        # Which execution plan produced these numbers (autotuner record:
        # cache key + knobs + predicted vs measured) — so adprof diffs can
        # say "the regression is plan A vs plan B", not just "it got slower".
        doc["plan"] = plan
    # PS-wire traffic, when the run mirrored any (the registry's ps.wire.*
    # counters): costmodel.calibrate derives the measured wire bandwidth
    # from these + the comm share — the interconnect term of predict().
    snap = _metrics.snapshot()
    wire = {key: snap[f"ps.wire.{key}"] for key in
            ("bytes_sent", "bytes_received", "bytes_saved",
             "bytes_quantized")
            if isinstance(snap.get(f"ps.wire.{key}"), (int, float))
            and snap[f"ps.wire.{key}"] > 0}
    # The compressor's host seconds live under its own wire.* prefix (it is
    # not transport traffic); calibrate's quantize_bytes_per_s fit reads
    # bytes_quantized / quantize_s out of this same block.
    qs = snap.get("wire.quantize_s")
    if isinstance(qs, (int, float)) and qs > 0:
        wire["quantize_s"] = qs
    if wire:
        doc["wire"] = wire
    if extra:
        doc.update(extra)
    return doc


def write_profile(path: str,
                  extra: Optional[Dict[str, Any]] = None) -> str:
    """Write the per-run profile JSON to ``path``; returns ``path``. The
    document is self-contained — ``tools/adprof.py`` and
    :mod:`telemetry.costmodel` read it with no live process."""
    doc = profile_document(extra)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, default=str)
    logging.info("profiling: wrote profile (%d program(s), %d period(s)) "
                 "to %s", len(doc["programs"]), len(doc["periods"]), path)
    return path


_WRITE_SEQ = 0


def maybe_write_profile() -> Optional[str]:
    """End-of-run hook (``train()`` calls it): write a profile into
    ``AUTODIST_PROFILE_DIR`` when profiling is active and the flag names a
    directory; no-op (None) otherwise. A failed write logs and returns None —
    diagnostics must never take down the run they describe."""
    global _WRITE_SEQ
    if not _STATE.enabled:
        return None
    out_dir = str(const.ENV.AUTODIST_PROFILE_DIR.val)
    if not out_dir:
        return None
    proc = int(const.ENV.AUTODIST_PROCESS_ID.val)
    try:
        os.makedirs(out_dir, exist_ok=True)
        # pid + per-process seq: concurrent runs sharing a dir never clobber
        # (the recorder's snap-dir collision class).
        path = os.path.join(
            out_dir, f"profile-w{proc}-p{os.getpid()}-{_WRITE_SEQ:03d}.json")
        _WRITE_SEQ += 1
        return write_profile(path)
    except (OSError, ValueError, TypeError) as e:
        logging.warning("profiling: profile write failed: %s", e)
        return None


# AUTODIST_PROFILE=1 arms the attribution plane at import (and with it span
# recording), mirroring AUTODIST_TELEMETRY's contract — worker processes
# launched with an inherited env profile without code changes.
if const.ENV.AUTODIST_PROFILE.val:
    enable()
