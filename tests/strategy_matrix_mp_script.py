"""Cross-process strategy-matrix script (driver in test_multiprocess.py).

The reference's 2-machine CI stage ran its full strategy dict across nodes
(``tests/integration/test_dist.py:14-42``, ``Jenkinsfile:91-131``). This script
is the TPU-native equivalent for the lowerings whose cross-process sharding is
non-trivial:

- ``ps``          — PS/ZeRO: Adam opt state physically sharded along ``reduce``
                    across the 2-process mesh.
- ``partitioned`` — UnevenPartitionedPS: model-axis storage including a
                    padded-uneven parameter (7 rows on a 2-way model axis).
- ``parallax``    — the explicit ``shard_map`` lowering: sparse (indices, rows)
                    wire for the embedding + BF16_EF compressed dense params.

Each config runs 3 steps through the public API. Two modes, selected by env
``AUTODIST_MATRIX_SINGLE``:

- unset: 2-process mode — the chief runs this script, the Coordinator
  re-executes it as the worker, both join one ``jax.distributed`` program over
  a 4-device (2 proc x 2 CPU devices) mesh.
- "1": single-process reference — same strategy on a 4-device single-process
  mesh. Identical global mesh => identical shard count => identical collective
  and bf16-rounding behavior, so the 2-process run must match value-exactly.

The chief writes final logical params, per-step losses, and physical-sharding
evidence (shard shapes, padded storage shapes, sparse-wire/EF flags) to the
JSON path in argv[1]; argv[2] picks the config.

An optional argv[3] phase drives the checkpoint legs (the reference's c10
2-node NFS saver contract, ``tests/integration/cases/c10.py:1-12``, against
cross-process-sharded state). ``AUTODIST_MATRIX_CKPT_DIR`` names the shared
checkpoint directory:

- ``ckpt_save``     — steps 0..2, then every process calls ``Saver.save``
                      (collective sharded save) and the program EXITS (the kill).
- ``ckpt_restore``  — a fresh 2-process program restores the latest checkpoint
                      (each process placing its own shards) and continues
                      steps 3..4.
- ``straight``      — 5 uninterrupted steps (the value-exact reference).
- ``train_save`` / ``train_resume`` — same protocol driven entirely through
  ``training.train`` (collective save + automatic resume inside the loop).
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

from autodist_tpu import AutoDist  # noqa: E402
from autodist_tpu.resource_spec import ResourceSpec  # noqa: E402
from autodist_tpu.strategy import (AllReduce, PS, Parallax,  # noqa: E402
                                   PartitionedAR, UnevenPartitionedPS)

BATCH = 16
LR = 0.05
STEPS = 3
STEPS_TOTAL = 5   # checkpoint legs: save after 3, continue to 5
VOCAB, DIM = 33, 4

SINGLE = os.environ.get("AUTODIST_MATRIX_SINGLE") == "1"
# Process count for the distributed mode (2 devices per process). The single-
# process reference uses one node with the same GLOBAL device count, so the
# mesh — and therefore collective/rounding behavior — is identical.
PROCS = int(os.environ.get("AUTODIST_MATRIX_PROCS", "2"))


def _spec(mesh=None):
    if SINGLE:
        nodes = [{"address": "localhost", "tpus": 2 * PROCS, "chief": True}]
    else:
        # Node addresses must be unique (the reference's cluster-spec key
        # contract); distinct 127/8 loopback IPs model multiple processes on
        # one host and all take the local launch fast path.
        nodes = [{"address": "localhost", "tpus": 2, "chief": True}] + \
                [{"address": f"127.0.0.{i + 2}", "tpus": 2}
                 for i in range(PROCS - 1)]
    info = {"nodes": nodes}
    if mesh:
        info["mesh"] = mesh
    return ResourceSpec(resource_info=info)


def make_batch(step: int):
    rng = np.random.RandomState(2000 + step)
    return {"idx": rng.randint(0, VOCAB, (BATCH,)),
            "x": rng.randn(BATCH, 7).astype(np.float32),
            "y": rng.randn(BATCH, DIM).astype(np.float32)}


def make_params():
    rng = np.random.RandomState(5)
    return {"emb": rng.randn(VOCAB, DIM).astype(np.float32) * 0.1,
            "wu": rng.randn(7, DIM).astype(np.float32) * 0.1,   # uneven dim0
            "w2": rng.randn(DIM, DIM).astype(np.float32) * 0.1,
            "b": np.zeros((DIM,), np.float32)}


def loss_fn(p, b):
    rows = jnp.take(p["emb"], b["idx"], axis=0)        # sparse gather
    h = rows + b["x"] @ p["wu"]
    pred = h @ p["w2"] + p["b"]
    return jnp.mean((b["y"] - pred) ** 2)


CONFIGS = {
    # PS/ZeRO: full weight-update sharding; Adam states shard along reduce.
    "ps": dict(builder=lambda: PS(), mesh=None,
               optimizer=lambda: optax.adam(1e-2)),
    # Model-axis storage with a padded-uneven param (7 -> 8 over 2 shards);
    # Adam, so the moments live padded + model-sharded across processes too.
    "partitioned": dict(builder=lambda: UnevenPartitionedPS(),
                        mesh={"model": 2, "data": -1},
                        optimizer=lambda: optax.adam(1e-2)),
    # Explicit shard_map lowering: sparse wire + BF16_EF on dense grads.
    "parallax": dict(
        builder=lambda: Parallax(compressor="HorovodCompressorEF"),
        mesh=None, optimizer=lambda: optax.sgd(LR)),
    # Hierarchical two-phase reduce across the process boundary: the inner
    # `reduce` axis lies within each process's 2 devices (the ICI tier on a
    # real pod), the outer `data` axis spans the two processes (the DCN tier).
    # jax.devices() lists process 0's devices first, so the row-major [data,
    # reduce] mesh puts reduce innermost-per-process by construction.
    "dcn": dict(
        builder=lambda: AllReduce(all_reduce_spec="DCN",
                                  compressor="HorovodCompressor",
                                  chunk_size=4),
        mesh={"data": 2, "reduce": 2},
        optimizer=lambda: optax.sgd(LR)),
    # Low-rank PowerSGD factors (P/Q matmuls + QR + two factor pmeans) across
    # the process boundary; deterministic, so exact vs single-process.
    "powersgd": dict(
        builder=lambda: AllReduce(compressor="PowerSGDCompressor",
                                  power_sgd_rank=2),
        mesh=None, optimizer=lambda: optax.sgd(LR)),
    # The 3-tier mesh for the 4-process leg (AUTODIST_MATRIX_PROCS=4,
    # 8 devices): model axis INSIDE each process's 2 devices (padded-uneven
    # storage never crosses a process), reduce ACROSS process pairs (Adam
    # moments ZeRO-sharded over the process boundary), data across the pair
    # groups. Mesh axis order is (data, reduce, model) row-major over
    # jax.devices(), which lists processes in order — so the coordinates
    # land exactly there by construction.
    "tp_zero": dict(builder=lambda: UnevenPartitionedPS(),
                    mesh={"model": 2, "reduce": 2, "data": -1},
                    optimizer=lambda: optax.adam(1e-2)),
    # PartitionedAR: model-axis storage sharding (incl. padded-uneven wu,
    # 7 -> 8) with all-reduce gradient sync. Canonical axis order puts data
    # outermost, so on 2 processes the model shards live IN-process and the
    # per-shard gradient all-reduce is what crosses the boundary — the
    # partitioned-storage + cross-process-AR lowering the other configs
    # don't cover. (tp_zero is the config whose storage spans processes.)
    "par": dict(builder=lambda: PartitionedAR(),
                mesh={"model": 2, "data": -1},
                optimizer=lambda: optax.adam(1e-2)),
}


def _shard_evidence(state, runner):
    """Physical-sharding facts the driver asserts (chief's local view)."""
    from autodist_tpu.parallel.synchronization import EFState
    ev = {}
    w2_opt_shards = None
    for leaf in jax.tree_util.tree_leaves(state.opt_state):
        if getattr(leaf, "ndim", 0) == 2 and leaf.shape[-1] == DIM \
                and leaf.shape[0] == DIM:
            w2_opt_shards = sorted({tuple(s.data.shape)
                                    for s in leaf.addressable_shards})
            break
    ev["w2_opt_shard_shapes"] = w2_opt_shards
    ev["wu_storage_shape"] = list(state.params["wu"].shape)
    ev["wu_shard_shapes"] = sorted({tuple(s.data.shape)
                                    for s in state.params["wu"].addressable_shards})
    ev["sparse_wire_params"] = sorted(runner.plan.sparse_wire_params)
    ef = state.ef_state
    leaves = jax.tree_util.tree_leaves(
        ef, is_leaf=lambda x: isinstance(x, EFState))
    ev["ef_params_dp"] = sorted(
        int(l.error.shape[0]) for l in leaves if isinstance(l, EFState))
    return ev


def main(out_path: str, config: str, phase: str = ""):
    cfg = CONFIGS[config]
    ad = AutoDist(_spec(cfg["mesh"]), cfg["builder"]())
    params = make_params()
    runner = ad.create_distributed_session(
        loss_fn, params, cfg["optimizer"](), example_batch=make_batch(0))
    if not SINGLE:
        assert jax.process_count() == PROCS, \
            f"process_count={jax.process_count()} != {PROCS}"
    assert jax.device_count() == 2 * PROCS, \
        f"device_count={jax.device_count()} != {2 * PROCS}"

    ckpt_dir = os.environ.get("AUTODIST_MATRIX_CKPT_DIR")

    if phase in ("train_save", "train_resume"):
        # The whole c10 protocol driven through training.train: collective
        # sharded saves inside the loop, automatic latest-checkpoint resume.
        from autodist_tpu.training import train
        steps = STEPS if phase == "train_save" else STEPS_TOTAL
        state = train(runner, params, make_batch, steps=steps,
                      checkpoint_dir=ckpt_dir, checkpoint_name="trainloop",
                      save_every=10_000, log_every=0)
        if phase == "train_resume":
            assert int(state.step) == STEPS_TOTAL, int(state.step)
        _write_result(out_path, config, runner, state, losses=[],
                      extra={"step": int(state.step),
                             "ckpt_files": _ckpt_listing(ckpt_dir)})
        return

    from autodist_tpu.checkpoint.saver import Saver
    if phase == "ckpt_restore":
        latest = Saver.latest_checkpoint(ckpt_dir, name="model")
        assert latest is not None, f"no checkpoint under {ckpt_dir}"
        state = Saver().restore(latest, runner=runner)
        assert int(state.step) == STEPS, int(state.step)
        lo, hi = STEPS, STEPS_TOTAL
    else:
        state = runner.init(params)
        lo, hi = 0, (STEPS_TOTAL if phase == "straight" else STEPS)

    evidence = _shard_evidence(state, runner)
    losses = []
    for step in range(lo, hi):
        state, loss = runner.run(state, make_batch(step))
        losses.append(float(loss))

    if phase == "ckpt_save":
        # COLLECTIVE: every process writes the state shards it owns; the chief
        # publishes the manifest. The program exits right after — the "kill".
        Saver().save(state, os.path.join(ckpt_dir, "model"), runner=runner)
        evidence["ckpt_files"] = _ckpt_listing(ckpt_dir)

    _write_result(out_path, config, runner, state, losses, extra=evidence)


def _ckpt_listing(ckpt_dir):
    if jax.process_index() != 0:
        return []
    return sorted(os.listdir(ckpt_dir))


def _write_result(out_path, config, runner, state, losses, extra):
    if jax.process_index() != 0:
        return
    logical = jax.device_get(runner.logical_params(state))
    result = {
        "config": config,
        "losses": losses,
        "params": {k: np.asarray(v).tolist() for k, v in logical.items()},
        "process_count": jax.process_count(),
        "device_count": jax.device_count(),
        "mesh": {k: int(v) for k, v in dict(runner.mesh.shape).items()},
        **extra,
    }
    with open(out_path, "w") as f:
        json.dump(result, f)


def start_single_reference(out_path: str, config: str, workdir: str,
                           phase: str = ""):
    """Start this script once, single-process, on a sim mesh matching the
    multi-process run's global device count (2 devices per process);
    ``mp_env.collect`` waits for it."""
    from tests.mp_env import start_single_reference as start
    procs = int(os.environ.get("AUTODIST_MATRIX_PROCS", "2"))
    args = [os.path.abspath(__file__), out_path, config]
    if phase:
        args.append(phase)
    return start(args, workdir, device_count=2 * procs)


def run_single_reference(out_path: str, config: str, workdir: str,
                         timeout: int = 300, phase: str = ""):
    """``start_single_reference`` and wait: the completed process."""
    from tests.mp_env import collect
    return collect(start_single_reference(out_path, config, workdir, phase),
                   timeout)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], sys.argv[3] if len(sys.argv) > 3 else "")
