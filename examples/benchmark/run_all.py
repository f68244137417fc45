"""Run every measured benchmark config and print the README table's numbers.

One command reproduces the performance claims (the reference's benchmark suite
was likewise driven per-config by flags; this adds the sweep driver):

    python examples/benchmark/run_all.py                 # everything (~20 min)
    python examples/benchmark/run_all.py --only resnet50,bert_base
    python examples/benchmark/run_all.py --steps 30      # quicker, noisier

Each config runs in a fresh subprocess (one AutoDist instance per process, the
reference's own isolation rule) and reports its average throughput; results
print as a table and optionally a JSON file.

One process for each chip: this parent never initializes JAX (the device
count is probed in a child too) and runs its children one after another, so
each child finds the chip free. A parent that had touched JAX would hold it.
"""

import argparse
import json
import os
import re
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")

# --update_baseline refuses runs shorter than the sweep length: sub-sweep
# rates are noisy, and the baseline only ratchets up.
MIN_BASELINE_STEPS = 60


def _probe_devices():
    """(device_count, backend) of the platform the benchmark subprocesses will
    see — probed in a subprocess so run_all itself never initializes a chip."""
    probe = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(len(jax.devices()), jax.default_backend())"],
        capture_output=True, text=True)
    try:
        count, backend = probe.stdout.strip().split()[-2:]
        return int(count), backend
    except (ValueError, IndexError):
        return 1, "unknown"

# name -> (argv builder, unit, number regex over combined output)
RATE = r"([\d,]+\.?\d*)"
CONFIGS = {
    "flagship": (lambda s: [os.path.join(ROOT, "bench.py")],
                 "tokens/s", r'"value": ([\d.]+)'),
    "resnet50": (lambda s: [os.path.join(ROOT, "examples/benchmark/imagenet.py"),
                            "--model", "resnet50", "--strategy", "AllReduce",
                            "--batch_size", "256", "--steps", s, "--log_every", s],
                 "examples/s", RATE + r" examples/sec"),
    "vgg16": (lambda s: [os.path.join(ROOT, "examples/benchmark/imagenet.py"),
                         "--model", "vgg16", "--strategy", "PartitionedPS",
                         "--batch_size", "256", "--steps", s, "--log_every", s],
              "examples/s", RATE + r" examples/sec"),
    "densenet121": (lambda s: [os.path.join(ROOT, "examples/benchmark/imagenet.py"),
                               "--model", "densenet121", "--batch_size", "128",
                               "--steps", s, "--log_every", s],
                    "examples/s", RATE + r" examples/sec"),
    "inceptionv3": (lambda s: [os.path.join(ROOT, "examples/benchmark/imagenet.py"),
                               "--model", "inceptionv3", "--batch_size", "128",
                               "--steps", s, "--log_every", s],
                    "examples/s", RATE + r" examples/sec"),
    "bert_base": (lambda s: [os.path.join(ROOT, "examples/benchmark/bert.py"),
                             "--size", "base", "--batch_size", "2048",
                             "--accum", "8", "--steps", s, "--log_every", s],
                  "examples/s", RATE + r" examples/sec"),
    "bert_large": (lambda s: [os.path.join(ROOT, "examples/benchmark/bert.py"),
                              "--size", "large", "--batch_size", "128",
                              "--steps", s, "--log_every", s],
                   "examples/s", RATE + r" examples/sec"),
    "lm1b_lstm": (lambda s: [os.path.join(ROOT, "examples/lm1b/lm1b_train.py"),
                             "--model", "lstm", "--steps", s, "--log_every", s],
                  "words/s", RATE + r" words/sec"),
    "ncf": (lambda s: [os.path.join(ROOT, "examples/benchmark/ncf.py"),
                       "--steps", s, "--log_every", s],
            "examples/s", RATE + r" examples/sec"),
    "moe": (lambda s: [os.path.join(ROOT, "examples/moe_lm.py"),
                       "--batch_size", "512", "--accum", "4",
                       "--steps", s, "--log_every", s],
            "tokens/s", RATE + r" tokens/sec"),
}


def run_config(name: str, steps: str, attempts: int = 2):
    builder, unit, pattern = CONFIGS[name]
    cmd = [sys.executable] + builder(steps)
    for attempt in range(attempts):
        proc = subprocess.run(cmd, capture_output=True, text=True)
        out = proc.stdout + proc.stderr
        if proc.returncode == 0:
            break
        # Transient platform failures (HBM-margin OOM right after another
        # config's process released memory) deserve one retry before the row
        # reads FAILED.
        if attempt < attempts - 1:
            print(f"  {name}: attempt {attempt + 1} failed, retrying ...",
                  flush=True)
    if proc.returncode != 0:
        return {"name": name, "unit": unit, "rate": None, "mfu_pct": None,
                "error": out.strip().splitlines()[-1] if out.strip() else "failed"}
    matches = re.findall(pattern, out)
    if not matches:
        return {"name": name, "unit": unit, "rate": None, "mfu_pct": None,
                "error": "no rate found in output"}
    rate = float(matches[-1].replace(",", ""))
    # Scripts print a shared "mfu N.NN%" line (flops.report_mfu); bench.py
    # reports the fraction in its JSON line instead.
    mfu_pct = None
    m = re.findall(r"mfu ([\d.]+)%", out)
    if m:
        mfu_pct = float(m[-1])
    else:
        m = re.findall(r'"mfu": ([\d.]+)', out)
        if m:
            mfu_pct = round(100.0 * float(m[-1]), 2)
    return {"name": name, "unit": unit, "rate": rate, "mfu_pct": mfu_pct,
            "error": None}


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--only", type=str, default="",
                        help="comma-separated subset of: " + ",".join(CONFIGS))
    parser.add_argument("--steps", type=int, default=60,
                        help="steps per config (flagship runs bench.py, which "
                             "has its own fixed length and ignores this)")
    parser.add_argument("--json", type=str, default="",
                        help="also write results to this JSON file")
    parser.add_argument("--list", action="store_true", help="list configs and exit")
    parser.add_argument("--baseline", type=str,
                        default=os.path.join(ROOT, "PERF_BASELINE.json"),
                        help="recorded-best snapshot to diff against "
                             "('' disables the comparison)")
    parser.add_argument("--update_baseline", action="store_true",
                        help="raise snapshot rows that this run beat "
                             "(never lowers a row)")
    args = parser.parse_args(argv)

    if args.list:
        for name in CONFIGS:
            print(name)
        return []

    if args.update_baseline and args.steps < MIN_BASELINE_STEPS:
        # Reject the combination BEFORE the (potentially hour-long) run, not
        # after it: short runs are noisy, and the baseline only ratchets up.
        parser.error(f"--update_baseline needs --steps >= {MIN_BASELINE_STEPS}"
                     f": a ratcheted noise outlier makes every honest later "
                     f"run read as a regression")

    names = [n.strip() for n in args.only.split(",") if n.strip()] or list(CONFIGS)
    unknown = [n for n in names if n not in CONFIGS]
    if unknown:
        parser.error(f"unknown configs {unknown}; valid: {sorted(CONFIGS)}")

    results = []
    for name in names:
        print(f"running {name} ...", flush=True)
        results.append(run_config(name, str(args.steps)))

    # Regression gate: diff each row against the recorded best. Steps below
    # the sweep length are noisier, so the gate only annotates — failures
    # stay human decisions; the >threshold rows are impossible to miss.
    # The snapshot records PER-CHIP ACCELERATOR rates: normalize by device
    # count, and skip the comparison entirely on CPU (a different machine).
    baseline = {}
    snapshot = None
    threshold = 2.0
    n_dev, backend = _probe_devices()
    if backend in ("cpu", "unknown"):
        # "unknown" means the probe subprocess itself failed: comparing host
        # rates against recorded per-chip accelerator bests would print
        # spurious REGRESSION rows, so treat it like CPU — but surface the
        # probe failure instead of silently skipping.
        if backend == "unknown":
            print("\nWARNING: device probe failed (could not determine the "
                  "backend); PERF_BASELINE comparison skipped — recorded "
                  "bests are accelerator chip rates", file=sys.stderr)
        else:
            print("\n(CPU backend: PERF_BASELINE comparison skipped — "
                  "recorded bests are chip rates)")
    elif args.baseline and os.path.exists(args.baseline):
        with open(args.baseline) as f:
            snapshot = json.load(f)
        baseline = snapshot.get("rows", {})
        threshold = snapshot.get("threshold_pct", 2.0)
    elif args.baseline and args.update_baseline:
        # First measured run on a fresh checkout: start a snapshot so every
        # config gains a gate row now rather than never.
        snapshot = {"threshold_pct": threshold, "rows": {}}

    width = max(len(r["name"]) for r in results)
    regressions = []
    print()
    for r in results:
        if r["rate"] is None:
            print(f"{r['name']:<{width}}  FAILED: {r['error']}")
            continue
        mfu = (f"  mfu {r['mfu_pct']:.1f}%" if r.get("mfu_pct") is not None
               else "")
        delta = ""
        best = baseline.get(r["name"], {}).get("rate")
        if best:
            per_chip = r["rate"] / max(n_dev, 1)
            pct = 100.0 * (per_chip / best - 1.0)
            r["vs_best_pct"] = round(pct, 2)
            delta = f"  {pct:+.1f}% vs best"
            if pct < -threshold:
                delta += "  << REGRESSION"
                regressions.append((r["name"], pct))
        print(f"{r['name']:<{width}}  {r['rate']:>14,.1f} {r['unit']}{mfu}{delta}")
    if regressions:
        print(f"\n{len(regressions)} row(s) regressed more than {threshold}% "
              f"vs {args.baseline}: "
              + ", ".join(f"{n} ({p:+.1f}%)" for n, p in regressions))
    if args.update_baseline and snapshot is not None:
        raised, created = [], []
        for r in results:
            per_chip = (r["rate"] / max(n_dev, 1)
                        if r["rate"] is not None else None)
            if per_chip is None:
                continue
            row = snapshot.setdefault("rows", {}).get(r["name"])
            if row is None:
                # A renamed/new benchmark config must enter the regression
                # gate on its first measured run, not silently escape it.
                snapshot["rows"][r["name"]] = {
                    "rate": round(per_chip, 1), "unit": r["unit"],
                    "recorded": "run_all --update_baseline (per-chip, new row)"}
                created.append(r["name"])
            elif per_chip > row["rate"]:
                row["rate"] = round(per_chip, 1)
                row["recorded"] = "run_all --update_baseline (per-chip)"
                raised.append(r["name"])
        if raised or created:
            with open(args.baseline, "w") as f:
                json.dump(snapshot, f, indent=1)
            if raised:
                print(f"baseline raised for: {', '.join(raised)}")
            if created:
                print(f"baseline rows created for: {', '.join(created)}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    return results


if __name__ == "__main__":
    main()
