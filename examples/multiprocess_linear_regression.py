"""Multi-process linear regression: the 2-process minimum slice.

Run directly as the chief (``python examples/multiprocess_linear_regression.py
out.json``); the Coordinator re-executes this same script as the worker with the
role env set — the reference's protocol of re-running ``python + sys.argv`` per
host (reference ``coordinator.py:66-90``).
Both processes join one ``jax.distributed`` coordination service (the TPU-native
replacement for the per-node ``tf.Server`` of reference ``cluster.py:160-210``),
build the global 4-device mesh (2 processes x 2 CPU devices), and run 3 SGD steps
of the minimum slice through the normal ``create_distributed_session`` path. The
chief writes final params + losses to the JSON path given in argv[1]; the pytest
driver asserts value-exact parity with a hand-computed single-process run.
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")  # runs on the virtual CPU mesh

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

from autodist_tpu import AutoDist  # noqa: E402
from autodist_tpu.strategy import AllReduce  # noqa: E402

# Default spec: two processes on one machine (the pytest / dryrun shape).
# SYS_RESOURCE_PATH (the reference's resource-spec env var, propagated to
# workers by the Coordinator) points at a spec FILE instead, so the same
# script drives the two-container distributed CI stage
# (docker/compose.dist.yml), where the worker is a separate host over ssh.
SPEC = os.environ.get("SYS_RESOURCE_PATH") or (
    "nodes: [{address: localhost, tpus: 2, chief: true}, "
    "{address: 127.0.0.1, tpus: 2}]")
BATCH = 16
LR = 0.1
STEPS = 3


def make_batch(step: int):
    rng = np.random.RandomState(1000 + step)
    x = rng.randn(BATCH).astype(np.float32)
    y = (3.0 * x + 2.0 + 0.1 * rng.randn(BATCH)).astype(np.float32)
    return {"x": x, "y": y}


def loss_fn(p, b):
    pred = b["x"] * p["w"] + p["b"]
    return jnp.mean((b["y"] - pred) ** 2)


def main(out_path: str):
    ad = AutoDist(SPEC, AllReduce())
    # numpy (not jnp) until the session exists: touching the XLA backend before
    # jax.distributed.initialize is illegal, and create_distributed_session is
    # what runs the multi-host bootstrap (the standard multi-host JAX constraint,
    # surfaced through the AutoDist session protocol).
    params = {"w": np.zeros((), np.float32), "b": np.zeros((), np.float32)}
    runner = ad.create_distributed_session(
        loss_fn, params, optax.sgd(LR), example_batch=make_batch(0))
    # The session setup must have joined both processes into one SPMD program.
    assert jax.process_count() == 2, f"process_count={jax.process_count()}"
    assert jax.device_count() == 4, f"device_count={jax.device_count()}"

    state = runner.init(params)
    losses = []
    for step in range(STEPS):
        state, loss = runner.run(state, make_batch(step))
        losses.append(float(loss))

    if jax.process_index() == 0:
        result = {
            "w": float(state.params["w"]),
            "b": float(state.params["b"]),
            "losses": losses,
            "process_count": jax.process_count(),
            "device_count": jax.device_count(),
        }
        with open(out_path, "w") as f:
            json.dump(result, f)


# Role env a chief subprocess must NOT inherit from its parent (a stale worker env
# would make it think it is a worker; a stale coordinator env would misroute init).
# The coordinator port is not here: run_two_process_chief always sets it fresh.
ROLE_ENV_VARS = ("AUTODIST_WORKER", "AUTODIST_STRATEGY_ID", "AUTODIST_PROCESS_ID",
                 "AUTODIST_NUM_PROCESSES", "AUTODIST_COORDINATOR_ADDR",
                 # A spec path exported while driving the docker dist stage must
                 # not leak into subprocess tests (it would swap their localhost
                 # spec for the container spec and try to ssh to 'worker').
                 "SYS_RESOURCE_PATH", "SYS_DATA_PATH")


def run_two_process_chief(out_path: str, workdir: str, timeout: int = 300,
                          attempts: int = 3, script: str = None,
                          extra_args=()):
    """Launch this script as the chief subprocess on a fresh port; the Coordinator
    inside it re-launches the worker. Shared by ``tests/test_multiprocess.py`` and
    ``__graft_entry__._dryrun_multiprocess`` so the env construction (clean role
    env, CPU platform, 2 local devices) stays in one place.
    Returns the completed chief process (check ``.returncode`` and read out_path).

    Port selection (bind ephemeral, close, reuse) has an inherent race: another
    process can claim the port before the coordinator binds it, so bind failures
    retry on a new port up to ``attempts`` times."""
    import socket
    import subprocess

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    for k in ROLE_ENV_VARS:
        env.pop(k, None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        # 2 local CPU devices per process -> 4 global devices across 2 processes.
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "AUTODIST_WORKING_DIR": workdir,
        # Run-by-path puts this file's dir on sys.path, not the repo root.
        "PYTHONPATH": repo_root + os.pathsep + env.get("PYTHONPATH", ""),
    })

    for attempt in range(attempts):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        env["AUTODIST_COORDINATOR_PORT"] = str(s.getsockname()[1])
        s.close()
        try:
            proc = subprocess.run(
                [sys.executable, script or os.path.abspath(__file__),
                 str(out_path), *extra_args],
                env=env, cwd=repo_root, capture_output=True, text=True,
                timeout=timeout)
        except subprocess.TimeoutExpired as e:
            # A missed gloo/coordination handshake (DEADLINE_EXCEEDED under
            # heavy host load, e.g. sharded CI) leaves both processes waiting
            # forever; a fresh attempt on a fresh port recovers.
            if attempt == attempts - 1:
                raise
            print(f"run_two_process_chief: attempt {attempt + 1} timed out "
                  f"({'DEADLINE_EXCEEDED' if e.stderr and b'DEADLINE_EXCEEDED' in e.stderr else 'no handshake error visible'}); retrying",
                  flush=True)
            continue
        retryable = proc.returncode != 0 and (
            "address already in use" in proc.stderr.lower()
            or "failed to bind" in proc.stderr.lower()
            or "deadline_exceeded" in proc.stderr.lower())
        if not retryable or attempt == attempts - 1:
            return proc
    return proc


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "/tmp/autodist_tpu/mp_lr_result.json")
