"""What every job and reader shares: finding a cell's files by the names in
``BENCHMARK.json``, the device check, completion fences, compile-cache
counters and the shape of the last line.

Everything that belongs to one configuration, one traffic mix, one model
family, one kind of job or one per-layer metric is a file of its own under a
directory of ``paths``; the harness finds it by name, so a later PR adds
files and entries and edits nothing here.
"""

import dataclasses
import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def work_dir(root: str = ROOT) -> str:
    """Where a run keeps what it writes besides the compile cache: inside
    the checkout, listed in .gitignore."""
    return os.path.join(root, ".bench_work")


class BenchmarkError(RuntimeError):
    """The run cannot produce a result: no line is printed, exit code 1."""


def process_age_s() -> float:
    """Seconds since this process was started, from the kernel's own record,
    so that the interpreter's start-up and the imports count as set-up."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything its names point to."""
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: str
    paths: List[str]

    def find(self, subdir: str, filename: str) -> str:
        return find_file(self.root, self.paths, subdir, filename)

    def load_module(self, subdir: str, name: str):
        return load_module(self.find(subdir, name + ".py"),
                           f"_bench_{subdir}_{name.replace('-', '_')}")


def find_file(root: str, paths: List[str], subdir: str, filename: str) -> str:
    for path in paths:
        candidate = os.path.join(root, path, subdir, filename)
        if os.path.isfile(candidate):
            return candidate
    raise BenchmarkError(f"no {subdir}/{filename} under any of {paths}")


def load_module(path: str, module_name: str):
    spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[module_name] = module
    spec.loader.exec_module(module)
    return module


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchmarkError(f"unknown workload {workload!r}; BENCHMARK.json "
                             f"has {sorted(cells)}")
    entry = cells[workload]
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == entry["config"])
    with open(os.path.join(root, config_entry["file"])) as f:
        config = json.load(f)
    paths = bench["paths"]
    traffic_file = find_file(root, paths, "traffic", entry["traffic"] + ".json")
    with open(traffic_file) as f:
        traffic = json.load(f)
    if traffic.get("chips", entry["chips"]) != entry["chips"]:
        raise BenchmarkError(
            f"{workload}: BENCHMARK.json asks for {entry['chips']} chip(s), "
            f"{traffic_file} is written for {traffic['chips']}")
    return Cell(
        name=workload, chips=entry["chips"], config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        root=root, paths=paths)


# ------------------------------------------------------------------ device

def describe_device() -> dict:
    """The device as JAX reports it: what the last line carries."""
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def check_device(device: dict, chips: int, require_tpu: bool):
    """No TPU, another device count than the cell asks for, or a kind with
    no published peaks: a failed run, never a CPU number."""
    from benchmark import peaks
    if require_tpu and device["platform"] != "tpu":
        raise BenchmarkError(
            f"no accelerator: JAX reports platform {device['platform']!r}. "
            f"The benchmark measures nothing on a CPU")
    if device["count"] != chips:
        raise BenchmarkError(f"{device['count']} device(s) visible, the cell "
                             f"asks for {chips}")
    if not require_tpu:
        return None          # CPU rehearsal from the tests: no peaks, no device metric
    return peaks.peaks_for(device["kind"])


def memory_peak_bytes() -> Optional[int]:
    """``peak_bytes_in_use`` on the fullest chip, where the backend says."""
    import jax
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in jax.local_devices()]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


def compiled_facts(compiled) -> dict:
    """What a compiled step says of itself: memory, kernels, collectives.
    Shared by the job (the step as it runs) and the compile rehearsal (the
    step for a described chip)."""
    text = compiled.as_text()
    facts = {"tpu_custom_call": "tpu_custom_call" in text,
             "collectives": [c for c in ("all-reduce", "reduce-scatter",
                                         "all-gather", "collective-permute")
                             if c in text]}
    mem = compiled.memory_analysis()
    if mem is not None:
        facts["compiled_bytes"] = {
            k: int(getattr(mem, k + "_size_in_bytes"))
            for k in ("argument", "output", "alias", "temp", "generated_code")}
    return facts


def step_bytes(compiled_bytes: dict) -> int:
    """What the step needs on a device: arguments + temporaries + outputs,
    less the outputs that alias (donated) arguments."""
    b = compiled_bytes
    return b["argument"] + b["temp"] + b["output"] - b["alias"]


def fence(x) -> float:
    """Completion fence: block on the device, then read the value back."""
    import jax
    return float(jax.block_until_ready(x))


class CompileEvents:
    """Counts, while used as a context manager, the programs JAX asked its
    backend for (``requests``: compiled or loaded from the persistent cache)
    and the persistent cache's hits and misses (a miss is a program compiled
    and then written)."""

    def __init__(self):
        self.hits = self.misses = self.requests = 0

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def __enter__(self):
        from jax import monitoring
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc):
        from jax import monitoring
        monitoring.unregister_event_listener(self._on_event)
        monitoring.unregister_event_duration_listener(self._on_duration)


class Phases:
    """Set-up is most of what a check costs, so every run says on standard
    error where its set-up went: seconds and compile events by phase."""

    def __init__(self, events: CompileEvents):
        self.events = events
        self.t = time.perf_counter()
        self.seen = (0, 0, 0)

    def done(self, what: str) -> float:
        now = time.perf_counter()
        e = self.events
        counts = (e.requests, e.hits, e.misses)
        d = [a - b for a, b in zip(counts, self.seen)]
        elapsed, self.t, self.seen = now - self.t, now, counts
        log(f"{elapsed:7.2f}s  programs {d[0]} (cache hits {d[1]}, misses "
            f"{d[2]})  {what}")
        return elapsed


# --------------------------------------------------------------- last line

def result_line(cell: Cell, record: dict, trace: bool) -> dict:
    """The one JSON object the driver reads. ``--trace 0`` carries the
    cell's end-to-end metrics, ``--trace 1`` its per-layer metrics, each from
    the reader file named after it; a reader that finds nothing to read
    returns None and its metric is left out."""
    metrics = {}
    if trace:
        for m in cell.per_layer:
            reader = cell.load_module("layers", m["name"])
            value = reader.read(record)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            value = record["end_to_end"].get(m["name"])
            if value is None:
                raise BenchmarkError(f"the job gave no {m['name']}")
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    line = {"correct": bool(record["correct"]),
            "attempted": int(record["attempted"]),
            "failed": int(record["failed"]),
            "metrics": metrics, "device": record["device"]}
    if trace and record.get("breakdown"):
        line["breakdown"] = record["breakdown"]
    if record.get("checks"):
        line["checks"] = record["checks"]     # the driver ignores other keys
    return line


def log(message: str):
    """Progress goes to standard error; standard output ends in the result."""
    print(f"[bench {time.strftime('%H:%M:%S')}] {message}", file=sys.stderr,
          flush=True)
