#!/usr/bin/env python3
"""Print, from a device trace in the benchmark's recorded form (``run.py
--keep-trace FILE``), the self seconds a chip spends by operation group, the
largest first, and the collectives apart: what a reader under
``benchmark/layers/`` can lean on.

    python tools/trace_groups.py FILE [N]
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import trace_reduce  # noqa: E402


def main(argv):
    trace = trace_reduce.load_json(argv[0])
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
