"""Bounded-staleness async parameter-server training across two processes.

Run it directly (CPU backend, loopback "2-node" cluster):

    PYTHONPATH=. python examples/async_ps_train.py

What happens, all through the public API (no manual transport plumbing):

1. The chief builds ``PS(sync=True, staleness=2)`` for a 2-node resource spec.
   ``create_distributed_session`` detects the non-synchronous regime: the
   processes stay independent JAX programs joined by the chief's parameter
   service instead of one SPMD collective program (the reference's async PS
   regime, ``ps_synchronizer.py:387-458``, rode its grpc plane the same way).
2. The Coordinator re-executes THIS script on the second "node" with the
   worker role env and the PS transport address.
3. Both processes call ``step(batch)``. The chief steps its local worker slot;
   the worker process pulls parameters over the TCP transport, computes
   gradients on its own devices, and pushes them back. The chief's
   staleness gate keeps any worker at most ``STALENESS`` steps ahead of the
   slowest one.
4. Parameter pulls are version-conditional (``read_if_newer``): a worker whose
   gate opened with no intervening updates re-uses its cached tree instead of
   re-downloading identical parameters — the summary prints the wire bytes the
   cache saved.

The chief prints a summary: applied update count (= both processes' steps),
each side's losses, and the worker's transport wire accounting.
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")  # runs on the virtual CPU mesh

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

from autodist_tpu import AutoDist, const  # noqa: E402
from autodist_tpu.strategy import PS  # noqa: E402

# Two "nodes" on loopback; on a real cluster these are distinct hosts (plus
# ssh_config entries) and the same script runs unchanged on each.
SPEC = ("nodes: [{address: localhost, tpus: 2, chief: true}, "
        "{address: 127.0.0.1, tpus: 2}]")
STALENESS = 2
STEPS = 8
LR = 0.05
DIM = 64


def make_batch(step: int):
    rng = np.random.RandomState(100 + step)
    x = rng.randn(32, DIM).astype(np.float32)
    w_true = np.linspace(-1.0, 1.0, DIM, dtype=np.float32)[:, None]
    y = x @ w_true + 0.5 + 0.05 * rng.randn(32, 1).astype(np.float32)
    return {"x": x, "y": y}


def loss_fn(p, b):
    pred = b["x"] @ p["w"] + p["b"]
    return jnp.mean((b["y"] - pred) ** 2)


def main(steps: int, staleness: int, out_path: str = None):
    if not const.is_worker():
        # A stale report from a previous run must not mask a worker crash.
        try:
            os.remove(_worker_report_path())
        except FileNotFoundError:
            pass
    ad = AutoDist(SPEC, PS(sync=True, staleness=staleness))
    params = {"w": np.zeros((DIM, 1), np.float32),
              "b": np.zeros((1,), np.float32)}
    step = ad.function(loss_fn, params, optax.adam(LR),
                       example_batch=make_batch(0))

    role = "worker" if const.is_worker() else "chief"
    losses = []
    for i in range(steps):
        loss = float(step(make_batch(i)))
        losses.append(loss)
        print(f"[{role}] step {i}: loss={loss:.4f}")

    if const.is_worker():
        # The worker's step closure drives a RemotePSWorker over the transport;
        # report its wire accounting back to the chief via a scratch file.
        remote = getattr(step.runner, "_remote_worker", None)
        wire = getattr(remote, "wire_bytes", (0, 0)) if remote else (0, 0)
        report = {"worker_losses": losses, "wire_sent": wire[0],
                  "wire_received": wire[1]}
        with open(_worker_report_path(), "w") as f:
            json.dump(report, f)
        return

    # Chief: wait for the worker process, then summarize the shared service.
    if not ad._coordinator.join(timeout=300.0):
        raise RuntimeError("worker process did not finish")
    runner = step.runner
    deadline = time.time() + 30
    while runner.service.updates_applied < 2 * steps and time.time() < deadline:
        time.sleep(0.05)
    try:
        with open(_worker_report_path()) as f:
            worker = json.load(f)
    except FileNotFoundError:
        worker = {}
    summary = {
        "applied_updates": runner.service.updates_applied,
        "chief_steps": steps,
        "worker_steps": len(worker.get("worker_losses", [])),
        "chief_final_loss": losses[-1],
        "worker_final_loss": (worker.get("worker_losses") or [None])[-1],
        "worker_wire_sent_bytes": worker.get("wire_sent"),
        "worker_wire_received_bytes": worker.get("wire_received"),
    }
    print("async PS summary:", json.dumps(summary, indent=2))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(summary, f)
    assert summary["applied_updates"] == 2 * steps, summary


def _worker_report_path() -> str:
    return os.path.join(const.DEFAULT_WORKING_DIR, "async_ps_worker_report.json")


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--steps", type=int, default=STEPS)
    parser.add_argument("--staleness", type=int, default=STALENESS)
    parser.add_argument("--out", type=str, default=None)
    args, _ = parser.parse_known_args()
    main(args.steps, args.staleness, args.out)
