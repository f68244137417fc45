"""The set-up ledger: once-a-build work, booked by name whether or not
telemetry is on.

``telemetry.span`` is the loop's instrument: disabled it costs one attribute
read and records nothing. Set-up (strategy, plan, state placement, imports,
every traced, lowered and compiled program) is paid once on every fresh
machine and restart, mostly before anybody could have called
``telemetry.enable()``, so its seconds go to the registry always.
:func:`phase` is the one helper for that; ``utils/compile_cache.py``'s
``jax.monitoring`` listeners book the jit stages through :func:`close_interval`
into the same account; :func:`setup_report` reads the account back from a
registry snapshot (this process's, or one the ``stats`` opcode shipped).

Each second once. A thread's closed intervals (phases and jit stages alike,
on ``time.perf_counter``'s clock) are kept newest last; an interval that
closes takes out of its own *self* time whatever closed inside it. So a
phase's ``<name>`` counter is inclusive, its ``<name>.self`` counter is its
duration less the phases and jit stages that ran inside it, and the
``.self`` counters and ``jit.wall_s`` together add up to wall time.

Not for per-step code: a phase takes two registry lookups and two locked
increments at exit, and is meant for what runs once a build.
"""

import os
import threading
import time
from typing import Dict, List, Optional, Tuple

from autodist_tpu.telemetry import metrics as _metrics
from autodist_tpu.telemetry import spans as _spans

__all__ = ["phase", "close_interval", "package_imported", "setup_report",
           "format_setup_report", "mark_setup_end"]

SELF_SUFFIX = ".self"
# Stages of getting a program that the jit listeners book a second once.
JIT_WALL = "jit.wall_s"
# What the account held when train() entered its loop.
BOOKED_AT_SETUP_END = "setup.booked_s"
PROGRAM_PREFIX = "jit.program."
PROGRAM_FIELDS = ("trace_s", "lower_s", "backend_s", "traces", "lowerings",
                  "backends", "cache_loads")
_MAX_CLOSED = 4096
_tls = threading.local()


def _closed_intervals() -> List[Tuple[float, float]]:
    closed = getattr(_tls, "closed", None)
    if closed is None:
        closed = _tls.closed = []
    return closed


def _self_seconds(closed: List[Tuple[float, float]], start: float,
                  end: float) -> float:
    """Close ``[start, end]`` on a thread's list: its duration less the
    intervals that closed inside it (which leave the list: whatever encloses
    this one later takes this one's whole duration out)."""
    inner = 0.0
    while closed and closed[-1][0] >= start:
        inner_start, inner_end = closed.pop()
        inner += inner_end - inner_start
    closed.append((start, end))
    del closed[:-_MAX_CLOSED]
    return max(end - start - inner, 0.0)


def close_interval(seconds: float) -> float:
    """An interval of ``seconds`` ended on this thread just now (a jit stage,
    reported by its listener at its end): the part of it that no interval
    closed before has booked, which the caller books."""
    end = time.perf_counter()
    return _self_seconds(_closed_intervals(), end - seconds, end)


class _Phase:
    """One phase, opened by ``__enter__`` (or in the past, at ``since``) and
    booked by ``__exit__`` on the same thread."""

    __slots__ = ("name", "_t0", "_span")

    def __init__(self, name: str, since: Optional[float]):
        self.name = name
        self._t0 = since
        self._span = None

    def __enter__(self):
        self._span = _spans.span(self.name)
        self._span.__enter__()
        if self._t0 is None:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._span.__exit__(*exc)
        own = _self_seconds(_closed_intervals(), self._t0, t1)
        _metrics.counter(self.name).inc(t1 - self._t0)
        _metrics.counter(f"{self.name}{SELF_SUFFIX}").inc(own)
        return False


def phase(name: str, since: Optional[float] = None) -> _Phase:
    """``with telemetry.phase("setup.plan_build_s"): ...`` — a
    ``telemetry.span(name)`` when telemetry is on and, on or off, the
    block's inclusive seconds in the counter ``name`` and its self seconds
    (less the phases and jit stages inside it) in ``<name>.self``.

    ``since`` (a ``time.perf_counter()`` reading) opens the phase in the
    past, for work that began before ``telemetry`` could be imported.
    For once-a-build work only (the module docstring says why)."""
    return _Phase(name, since)


_import_t0: Optional[float] = None


def package_imported(began: float):
    """``autodist_tpu.telemetry`` finished importing, having begun at
    ``began`` (``time.perf_counter()``): the ledger's first phase, and the
    reading ``setup.process_age_at_import_s`` is later taken back to."""
    global _import_t0
    _import_t0 = began
    with phase("setup.import_s", since=began):
        pass


def process_age_s() -> Optional[float]:
    """Seconds since the kernel started this process (what ran before the
    program's own clock: the interpreter, the caller's imports), or None
    where ``/proc`` does not say."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


# ------------------------------------------------------------------ report

def _program_rows(snap: Dict[str, object]) -> Dict[str, Dict[str, float]]:
    rows: Dict[str, Dict[str, float]] = {}
    for key, value in snap.items():
        if not key.startswith(PROGRAM_PREFIX):
            continue
        name, _, field = key[len(PROGRAM_PREFIX):].rpartition(".")
        if field in PROGRAM_FIELDS and name:
            rows.setdefault(name, dict.fromkeys(PROGRAM_FIELDS, 0))[field] = value
    return rows


def setup_report(snap: Optional[Dict[str, object]] = None) -> Dict[str, object]:
    """The set-up account of a registry snapshot (default: this process's
    now) as plain data: ``phases`` (``{name: {"s", "self_s"}}``), ``jit``
    (the four sums and ``wall_s``), ``step`` (the runner's step programs:
    seconds and times each stage ran), ``programs`` (every other program by
    name, dearest first, ``other`` = the names past the table's bound),
    ``booked_s`` (the ``.self`` counters plus ``jit.wall_s``: each second
    once), ``booked_at_setup_end_s`` (that sum as ``train()`` froze it when
    it entered its loop; None before) and ``process_age_at_import_s``."""
    if snap is None:
        snap = _metrics.registry().snapshot()
    phases = {}
    for key, value in snap.items():
        if key.endswith(SELF_SUFFIX) and isinstance(value, (int, float)):
            name = key[:-len(SELF_SUFFIX)]
            phases[name] = {"s": float(snap.get(name, 0.0)),
                            "self_s": float(value)}
    jit = {field: float(snap.get(f"jit.{field}", 0.0))
           for field in ("trace_s", "lower_s", "backend_s", "wall_s")}
    jit["programs"] = int(snap.get("jit.programs", 0))
    step = {field: snap.get(f"jit.step.{field}", 0) for field in
            ("trace_s", "lower_s", "backend_s", "traces", "lowerings")}
    rows = _program_rows(snap)
    step_names = sorted(n for n in rows
                        if snap.get(f"{PROGRAM_PREFIX}{n}.is_step"))
    programs = [dict(row, name=name,
                     total_s=row["trace_s"] + row["lower_s"] + row["backend_s"])
                for name, row in rows.items() if name not in step_names]
    programs.sort(key=lambda row: (-row["total_s"], row["name"]))
    return {
        "phases": phases, "jit": jit, "step": step,
        "step_programs": step_names, "programs": programs,
        "booked_s": sum(p["self_s"] for p in phases.values()) + jit["wall_s"],
        "booked_at_setup_end_s": snap.get(BOOKED_AT_SETUP_END),
        "process_age_at_import_s": snap.get("setup.process_age_at_import_s"),
        "kernel_call_sites": int(snap.get("jit.kernel_call_sites", 0)),
    }


def format_setup_report(report: Dict[str, object]) -> str:
    """One line for the log: total booked, the five largest ``.self`` phases,
    the step program's stages, the three dearest other programs."""
    phases = sorted(report["phases"].items(),
                    key=lambda item: -item[1]["self_s"])[:5]
    step = report["step"]
    others = report["programs"][:3]
    return ("set-up %.1fs booked | self: %s | step: trace+lower %.1fs "
            "(traced x%d, lowered x%d) backend %.1fs | other programs: %s "
            "| %d kernel call sites" % (
                report["booked_s"],
                ", ".join(f"{name} {p['self_s']:.1f}s" for name, p in phases)
                or "-",
                step["trace_s"] + step["lower_s"], step["traces"],
                step["lowerings"], step["backend_s"],
                ", ".join(f"{row['name']} {row['total_s']:.1f}s"
                          for row in others) or "-",
                report["kernel_call_sites"]))


def mark_setup_end() -> Dict[str, object]:
    """``train()`` is about to enter its loop, whose first act is the first
    pull from the batch source: set-up is over. Freezes what the account
    holds in the gauge ``setup.booked_s`` (what a reader divides by its own
    clock's set-up seconds) and logs the one line."""
    from autodist_tpu.utils import logging
    age = process_age_s()
    if age is not None and _import_t0 is not None:
        # How old the process was when the package's import began: the
        # interpreter and the caller's imports, before any clock of ours.
        _metrics.gauge("setup.process_age_at_import_s").set(
            max(age - (time.perf_counter() - _import_t0), 0.0))
    report = setup_report()
    _metrics.gauge(BOOKED_AT_SETUP_END).set(report["booked_s"])
    report["booked_at_setup_end_s"] = report["booked_s"]
    logging.info("train: %s", format_setup_report(report))
    return report
