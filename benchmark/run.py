#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on the machine that holds the chips the cell
asks for. The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (``--trace 0``: the cell's end-to-end
metrics; ``--trace 1``: its per-layer metrics, from a run that traces a few
seconds late in the window), ``device`` and, traced, ``breakdown``. No TPU,
another chip count than the cell asks for, a device without published peaks,
or any error: no result line and a non-zero exit code. Progress goes to
standard error.

The cell's files are found by the names in ``BENCHMARK.json``
(``benchmark/harness.py``); the job that runs it is ``jobs/<job>.py``, named
in the traffic file.
"""

import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(_HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

CLOCK0 = (harness.process_age_s(), time.perf_counter())


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: str = ROOT, require_tpu: bool = True,
             keep_trace: str = "") -> dict:
    """Returns the last line as a dict. ``require_tpu=False`` is for the
    tests' CPU rehearsal at a tiny size: no peaks, so no device metric."""
    cell = harness.load_cell(workload, root)
    if require_tpu:
        # Only the checkout outlasts a run, so the compile cache lives in it,
        # at a fixed path (the path is part of the cache's key), whatever
        # the machine came with. The program reads this variable and sets
        # no directory of its own when it is there.
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # The program's working directory (strategy files, by default under
    # /tmp/autodist_tpu) goes inside the checkout too: two checkouts on one
    # machine share nothing.
    os.environ.setdefault("AUTODIST_WORKING_DIR", harness.work_dir(root))
    from autodist_tpu.utils import compile_cache
    compile_cache.configure()     # before the first compile, so all are kept
    device = harness.describe_device()
    peaks = harness.check_device(device, cell.chips, require_tpu)
    job = cell.load_module("jobs", cell.traffic["job"])
    record = job.run(cell, seed=seed, seconds=seconds, trace=trace,
                     require_tpu=require_tpu, clock0=CLOCK0, peaks=peaks,
                     keep_trace=keep_trace)
    return harness.result_line(cell, record, trace)


def main(argv=None) -> int:
    import argparse
    import json
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--keep-trace", default="", metavar="FILE",
                        help="with --trace 1, also write the device trace in "
                             "the tests' recorded form (.json.gz) to FILE")
    args = parser.parse_args(argv)
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace), keep_trace=args.keep_trace)
    except Exception:  # noqa: BLE001 — the boundary: no result line, exit 1
        import traceback
        traceback.print_exc()
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
