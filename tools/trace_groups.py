#!/usr/bin/env python3
"""Print, from a device trace in the benchmark's recorded form (``run.py
--keep-trace FILE``), the self seconds a chip spends by operation group, the
largest first, and the collectives apart: what a reader under
``benchmark/layers/`` can lean on.

    python tools/trace_groups.py FILE [N]
    python tools/trace_groups.py FILE --group 'fusion (kCustom)' [STEPS]

``--group`` takes one group apart by instruction (PERF.md section 5's
"by instruction"): each instruction's name, its calls in the window on the
first chip, the mean ms of a call and, given the traced ``STEPS``, its ms a
step, the dearest first.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import trace_reduce  # noqa: E402


def by_instruction(trace, group: str, steps: int):
    summary = trace_reduce.summarize(trace)
    ops = trace.devices[min(trace.devices)]
    calls = {}
    for op, seconds in trace_reduce.self_seconds(ops, summary.window):
        if trace_reduce.group(op) == group:
            n, total = calls.get(op.name, (0, 0.0))
            calls[op.name] = n + 1, total + seconds
    print(json.dumps({"group": group, "instructions": len(calls),
                      "ms_a_step": 1e3 * sum(t for _, t in calls.values()) / steps}))
    for name, (n, total) in sorted(calls.items(), key=lambda kv: -kv[1][1]):
        print(f"{1e3 * total / steps:9.4f} ms a step  {n:5d} calls of "
              f"{1e3 * total / n:8.4f} ms  {name}")


def main(argv):
    trace = trace_reduce.load_json(argv[0])
    if argv[1:2] == ["--group"]:
        return by_instruction(trace, argv[2], int(argv[3]) if argv[3:] else 1)
    top = int(argv[1]) if len(argv) > 1 else 40
    summary = trace_reduce.summarize(trace)
    print(json.dumps({"window_s": summary.window_s, "busy_s": summary.busy_s,
                      "collective_s": summary.mean("collective_s"),
                      "exposed_collective_s":
                          summary.mean("exposed_collective_s")}))
    for name, seconds in summary.top_ops(top):
        print(f"{seconds:10.4f}  {name}")
    collectives = {}
    for ops in trace.devices.values():
        for op, seconds in trace_reduce.self_seconds(ops, summary.window):
            if trace_reduce.is_collective(op):
                key = (trace_reduce.group(op), op.category)
                collectives[key] = collectives.get(key, 0.0) \
                    + seconds / len(trace.devices)
    for (name, category), seconds in sorted(collectives.items(),
                                            key=lambda kv: -kv[1]):
        print(f"collective {seconds:10.4f}  {name}  [{category}]")


if __name__ == "__main__":
    main(sys.argv[1:])
