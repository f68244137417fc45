"""From the profiler's device trace to numbers.

``read_xplane`` turns an ``.xplane.pb`` into plain records (one list of
operations per device, plus the host-side marks the job wrote); everything
after that is arithmetic on intervals and is checked on a small recorded
trace in ``tests/``. Times are seconds on the trace's own clock.

What counts as what, on a TPU trace as jax 0.9.0 / libtpu 0.0.34 write it:

* a device is a plane named ``/device:TPU:<n>``; its operations are the
  events of the line ``XLA Ops``, and an event's name is the whole HLO
  instruction, ``%fusion.12 = f32[..]{..} fusion(...), kind=kOutput, ...``.
  ``parse_hlo`` keeps the instruction's name and, as its category, the
  opcode with the fusion kind or the custom call's target;
* control-flow operations (``while``, ``conditional``, ``call``) enclose
  the operations of their bodies, so busy time is a *union* of the other
  operations' intervals and per-operation time is *self* time (duration
  less what is nested inside);
* a collective is an operation whose opcode starts with ``all-reduce``,
  ``reduce-scatter``, ``all-gather``, ``all-to-all`` or
  ``collective-permute`` (``-start``/``-done`` halves included);
* a Pallas (Mosaic) kernel is a custom call to ``tpu_custom_call``.
"""

import dataclasses
import gzip
import json
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MARK_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"^(all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute)")
CONTROL_FLOW = ("while", "conditional", "call")
PALLAS = "custom-call:tpu_custom_call"
# The opcode is the first lower-case word that follows white space and is
# followed by "(": shapes hold "T(8,128)" and "S(1)", never that.
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_FUSION_KIND = re.compile(r"\bkind=(k\w+)")
_CALL_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_TRAILING_ID = re.compile(r"[.\d]+$")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start: float
    dur: float
    category: str = ""     # opcode[:fusion kind | custom-call target]

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    devices: Dict[int, List[Op]]            # device ordinal -> operations
    marks: Dict[str, float]                 # "bench.<name>" -> start, seconds

    def window(self) -> Interval:
        """The traced window: between the two marks the job writes at fenced
        log boundaries, or, on a trace without them, the extent of the
        device operations."""
        if "bench.window_begin" in self.marks and "bench.window_end" in self.marks:
            return (self.marks["bench.window_begin"],
                    self.marks["bench.window_end"])
        ops = [op for d in self.devices.values() for op in d]
        return (min(op.start for op in ops), max(op.end for op in ops))


# ----------------------------------------------------------------- reading

def parse_hlo(text: str) -> Tuple[str, str]:
    """(instruction name, category) of an ``XLA Ops`` event's name."""
    name, sep, rest = text.partition(" = ")
    name = name.strip().lstrip("%")
    if not sep:
        return name, ""
    match = _OPCODE.search(" " + rest)
    opcode = match.group(1) if match else ""
    detail = None
    if opcode == "fusion":
        detail = _FUSION_KIND.search(rest)
    elif opcode == "custom-call":
        detail = _CALL_TARGET.search(rest)
    return name, f"{opcode}:{detail.group(1)}" if detail else opcode


def read_xplane(path: str) -> Trace:
    """Needs nothing but JAX. Picoseconds and nanoseconds become seconds."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[int, List[Op]] = {}
    marks: Dict[str, float] = {}
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for event in line.events:
                    name, category = parse_hlo(event.name)
                    ops.append(Op(name, event.start_ns * 1e-9,
                                  event.duration_ns * 1e-9, category))
            devices[int(match.group(1))] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for event in line.events:
                    if event.name.startswith(MARK_PREFIX):
                        marks.setdefault(event.name, event.start_ns * 1e-9)
    return Trace(devices=devices, marks=marks)


def save_json(trace: Trace, path: str):
    """The recorded form the tests keep: small, readable, no protobuf."""
    doc = {"marks": trace.marks,
           "devices": {str(d): [[op.name, op.start, op.dur, op.category]
                                for op in ops]
                       for d, ops in trace.devices.items()}}
    with gzip.open(path, "wt") as f:
        json.dump(doc, f)


def load_json(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    return Trace(devices={int(d): [Op(*row) for row in ops]
                          for d, ops in doc["devices"].items()},
                 marks=doc["marks"])


# --------------------------------------------------------------- intervals

def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union."""
    out: List[Interval] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def total(merged: Sequence[Interval]) -> float:
    return sum(end - start for start, end in merged)


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of merged ``a`` that merged ``b`` does not cover."""
    out: List[Interval] = []
    j = 0
    for start, end in a:
        cursor = start
        while j < len(b) and b[j][1] <= cursor:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cursor:
                out.append((cursor, b[k][0]))
            cursor = max(cursor, b[k][1])
            k += 1
        if cursor < end:
            out.append((cursor, end))
    return out


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    return subtract([window], busy)


# ------------------------------------------------------------ classification

def is_collective(op: Op) -> bool:
    return bool(COLLECTIVE.match(op.category))


def is_control_flow(op: Op) -> bool:
    return op.category in CONTROL_FLOW


def is_pallas(op: Op) -> bool:
    """A Mosaic kernel. No ``pallas_call`` in the program carries a
    ``name=`` today; the instruction's name comes from whatever scope the
    call sat in (``attn``, ``jvp__``, ``transpose_jvp___``), which no metric
    may lean on, so the kernels are counted together."""
    return op.category == PALLAS


def group(op: Op) -> str:
    """The name under which the breakdown sums an operation: the
    instruction's name less its numeric suffix, with the fusion kind (XLA
    names a fusion after what it holds: ``convolution_add_fusion``,
    ``multiply_reduce_fusion``), and ``pallas:`` before a Mosaic kernel."""
    base = _TRAILING_ID.sub("", op.name) or op.name
    if is_pallas(op):
        return "pallas:" + base
    _, _, detail = op.category.partition(":")
    return f"{base} ({detail})" if detail else base


# ---------------------------------------------------------------- reduction

def self_seconds(ops: Sequence[Op], window: Optional[Interval] = None
                 ) -> List[Tuple[Op, float]]:
    """Each operation with its self time: its duration less the operations
    nested inside it (a ``while`` holds its body's operations)."""
    if window is not None:
        ops = [op for op in ops if op.start >= window[0] and op.end <= window[1]]
    order = sorted(ops, key=lambda op: (op.start, -op.dur))
    selfs = [op.dur for op in order]
    stack: List[int] = []
    for i, op in enumerate(order):
        while stack and order[stack[-1]].end <= op.start:
            stack.pop()
        if stack and op.end <= order[stack[-1]].end + 1e-12:
            selfs[stack[-1]] -= op.dur
        stack.append(i)
    return [(op, max(s, 0.0)) for op, s in zip(order, selfs)]


def busy_intervals(ops: Sequence[Op], window: Interval) -> List[Interval]:
    return merge(clip(((op.start, op.end) for op in ops), window))


@dataclasses.dataclass
class DeviceSummary:
    busy_s: float
    collective_s: float           # union of collective operations
    exposed_collective_s: float   # ... with no other operation running
    pallas_s: float               # self time of Mosaic kernels
    by_group: Dict[str, float]    # self seconds by group()
    idle: List[Interval]          # gaps of the busy union, longest first


def summarize_device(ops: Sequence[Op], window: Interval) -> DeviceSummary:
    leaf = [op for op in ops if not is_control_flow(op)]
    busy = busy_intervals(leaf, window)
    coll = merge(clip(((op.start, op.end) for op in leaf if is_collective(op)),
                      window))
    other = merge(clip(((op.start, op.end) for op in leaf
                        if not is_collective(op)), window))
    by_group: Dict[str, float] = {}
    pallas = 0.0
    for op, seconds in self_seconds(ops, window):
        if is_control_flow(op):
            continue          # its self time is sequencing, shown as idle
        by_group[group(op)] = by_group.get(group(op), 0.0) + seconds
        if is_pallas(op):
            pallas += seconds
    idle = sorted(gaps(busy, window), key=lambda g: g[0] - g[1])
    return DeviceSummary(
        busy_s=total(busy), collective_s=total(coll),
        exposed_collective_s=total(subtract(coll, other)), pallas_s=pallas,
        by_group=by_group, idle=idle)


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    window: Interval
    devices: Dict[int, DeviceSummary]

    def mean(self, field: str) -> float:
        values = [getattr(d, field) for d in self.devices.values()]
        return sum(values) / len(values)

    @property
    def busy_s(self) -> float:
        return self.mean("busy_s")

    def worst_idle_device(self) -> Tuple[int, float]:
        d = min(self.devices, key=lambda k: self.devices[k].busy_s)
        return d, 1.0 - self.devices[d].busy_s / self.window_s

    def top_ops(self, n: int = 10) -> List[List]:
        """Self seconds by group, averaged over the devices, largest first."""
        sums: Dict[str, float] = {}
        for d in self.devices.values():
            for name, seconds in d.by_group.items():
                sums[name] = sums.get(name, 0.0) + seconds / len(self.devices)
        return [[name, seconds] for name, seconds in
                sorted(sums.items(), key=lambda kv: -kv[1])[:n]]


def summarize(trace: Trace) -> TraceSummary:
    if not trace.devices or not any(trace.devices.values()):
        raise ValueError("the trace holds no device operation")
    window = trace.window()
    return TraceSummary(
        window_s=window[1] - window[0], window=window,
        devices={d: summarize_device(ops, window)
                 for d, ops in trace.devices.items()})


def attribute_gaps(idle: Sequence[Interval],
                   spans: Sequence[Tuple[str, float, float]],
                   n: int = 10, longest: int = 200) -> List[List]:
    """Idle seconds by what the host was doing. Each of the ``longest`` gaps
    goes to the host span (name, start, end on the trace's clock) that
    covers most of it, or to "no span"; the many short gaps between one
    operation and the next are summed under one name of their own. Sums by
    name, largest first."""
    idle = sorted(idle, key=lambda g: g[0] - g[1])
    sums: Dict[str, float] = {}
    rest = total(idle[longest:])
    if rest:
        sums["between operations (short gaps)"] = rest
    for start, end in idle[:longest]:
        best, best_cover = "no span", 0.0
        for name, s0, s1 in spans:
            cover = min(end, s1) - max(start, s0)
            if cover > best_cover:
                best, best_cover = name, cover
        sums[best] = sums.get(best, 0.0) + (end - start)
    return [[name, seconds] for name, seconds in
            sorted(sums.items(), key=lambda kv: -kv[1])[:n]]
