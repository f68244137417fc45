"""Real 2-process execution of the distributed runtime.

The reference proved its cluster runtime by actually running it: a 2-machine CI
stage started real tf.Servers and re-executed the user script per node
(reference ``Jenkinsfile:91-131``, ``cluster.py:160-210``). The equivalent here
is two OS processes on the CPU backend: the chief runs
``examples/multiprocess_linear_regression.py``, the Coordinator re-launches the
same script as the worker (loopback, no SSH), both call
``maybe_initialize_multihost`` and join one
``jax.distributed`` coordination service, build a global 4-device mesh
(2 processes x 2 devices), and step the minimum slice with real cross-process
collectives (gloo). Value-exactness is asserted against a hand-computed
single-process SGD run — the reference's c0 criterion
(``tests/integration/cases/c0.py:88-121``) across a process boundary.

This file holds the strategy matrix; checkpoint / resume, sequence parallelism
and the examples are ``test_multiprocess_checkpoint.py`` (one file is one
``xdist`` worker's, so the two halves run side by side).
"""

import json

import numpy as np

import examples.multiprocess_linear_regression as mp_script
from tests.mp_env import run_matrix_config as _run_matrix_config


def _expected_params():
    """Hand-computed 3-step SGD on the full batch (closed form, pure numpy)."""
    w = b = 0.0
    losses = []
    for step in range(mp_script.STEPS):
        batch = mp_script.make_batch(step)
        x, y = batch["x"], batch["y"]
        resid = y - (w * x + b)
        losses.append(float(np.mean(resid ** 2)))
        w -= mp_script.LR * float(np.mean(-2.0 * x * resid))
        b -= mp_script.LR * float(np.mean(-2.0 * resid))
    return w, b, losses


def test_two_process_training_matches_single_process(tmp_path):
    out = tmp_path / "result.json"
    proc = mp_script.run_two_process_chief(str(out), str(tmp_path / "workdir"))
    assert proc.returncode == 0, (
        f"chief failed (rc={proc.returncode})\n"
        f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}")
    result = json.loads(out.read_text())

    assert result["process_count"] == 2
    assert result["device_count"] == 4
    want_w, want_b, want_losses = _expected_params()
    np.testing.assert_allclose(result["w"], want_w, rtol=1e-5)
    np.testing.assert_allclose(result["b"], want_b, rtol=1e-5)
    np.testing.assert_allclose(result["losses"], want_losses, rtol=1e-5)


def test_heterogeneous_device_counts_weighted_mean(tmp_path):
    """2 devices on the chief + 1 on the worker (the reference's r4.yml shape):
    the 3-shard batch split must produce exactly the full-batch gradient update
    (c0's weighted-mean assertion, tests/integration/cases/c0.py:110-120)."""
    import os

    import tests.hetero_mp_script as hetero

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "hetero_mp_script.py")
    out = tmp_path / "result.json"
    proc = mp_script.run_two_process_chief(
        str(out), str(tmp_path / "workdir"), script=script)
    assert proc.returncode == 0, (
        f"chief failed (rc={proc.returncode})\n"
        f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}")
    result = json.loads(out.read_text())
    assert result["device_count"] == 3

    w = b = 0.0
    for step in range(hetero.STEPS):
        batch = hetero.make_batch(step)
        x, y = batch["x"], batch["y"]
        resid = y - (w * x + b)
        w -= hetero.LR * float(np.mean(-2.0 * x * resid))
        b -= hetero.LR * float(np.mean(-2.0 * resid))
    np.testing.assert_allclose(result["w"], w, rtol=1e-5)
    np.testing.assert_allclose(result["b"], b, rtol=1e-5)


def test_cross_process_bounded_staleness_ps(tmp_path):
    """The c9 timing assertion across a real process boundary: a fast remote
    worker (own process, PS transport) completes exactly `staleness` steps ahead
    of the slow chief-side worker, then each further step blocks on the chief's
    gate until the slow worker advances (reference c9.py:92-126)."""
    import os
    import subprocess
    import sys

    import tests.async_ps_script as aps

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "async_ps_script.py")
    out = tmp_path / "async_result.json"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "AUTODIST_WORKING_DIR": str(tmp_path / "workdir"),
        "PYTHONPATH": os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        + os.pathsep + env.get("PYTHONPATH", ""),
    })
    from examples.multiprocess_linear_regression import ROLE_ENV_VARS
    for k in ROLE_ENV_VARS:
        env.pop(k, None)

    # The unblocked-steps-are-fast signature is wall-clock-based: a transient
    # host load spike (sharded CI saturating the core) can push an unblocked
    # step past the bound with the gate semantics perfectly healthy. The
    # CORRECTNESS assertions stay hard every attempt; only a failed timing
    # signature retries on a fresh run.
    for attempt in range(3):
        proc = subprocess.run([sys.executable, script, str(out)], env=env,
                              cwd=os.path.dirname(os.path.dirname(script)),
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, (
            f"chief failed (rc={proc.returncode})\n"
            f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}")
        result = json.loads(out.read_text())

        assert result["fast_steps"] == aps.FAST_STEPS
        assert result["slow_steps"] == aps.SLOW_STEPS
        # Every gradient from both processes was applied by the shared service.
        assert result["final_version"] == aps.FAST_STEPS + aps.SLOW_STEPS

        durations = result["durations"]
        # First `staleness` steps run unblocked (fast); each following step
        # must wait for the slow worker's ~SLOW_SLEEP cadence at the gate.
        fast, gated = durations[:aps.STALENESS], durations[aps.STALENESS:]
        timing_ok = (all(d < aps.SLOW_SLEEP * 0.6 for d in fast)
                     and all(d > aps.SLOW_SLEEP * 0.3 for d in gated))
        if timing_ok:
            break
        print(f"staleness timing signature failed under load "
              f"(attempt {attempt + 1}): {durations}; retrying")
    else:
        # Sustained host oversubscription can deschedule the fast worker for
        # seconds, letting the slow worker lap it — the wall-clock signature
        # is then legitimately absent (the gate never needed to block). The
        # gate SEMANTICS are still assertable without a clock: the version
        # read at the fast worker's k-th step already includes its own k
        # prior applies (step = pull->apply), so the slow worker's share is
        # v - k, and the gate bounds the fast worker's lead over it:
        # k - (v - k) <= staleness.
        versions = result["versions_read"]
        for k, v in enumerate(versions):
            assert 2 * k - v <= aps.STALENESS, (k, v, versions)
        print(f"timing signature unavailable under sustained load; "
              f"version invariant held: {versions}")


def test_cross_process_ps_zero_sharded_opt_state(tmp_path):
    """PS/ZeRO across 2 real processes: Adam moments physically sharded along
    the reduce axis that spans the process boundary, training value-exact."""
    single, two = _run_matrix_config(tmp_path, "ps")
    # w2 is (4,4); ZeRO shards dim0 over reduce=4, so the chief's 2 local
    # devices each hold a (1,4) tile of each Adam moment — across processes.
    assert two["w2_opt_shard_shapes"] == [[1, 4]]
    assert single["w2_opt_shard_shapes"] == [[1, 4]]


def test_cross_process_partitioned_padded_uneven_storage(tmp_path):
    """UnevenPartitionedPS across 2 real processes: the 7-row parameter lives
    padded to 8 on a model axis spanning both processes, each device holding a
    (4, DIM) tile; updates stay value-exact (pad rows masked)."""
    single, two = _run_matrix_config(tmp_path, "partitioned")
    assert two["wu_storage_shape"] == [8, 4]
    assert two["wu_shard_shapes"] == [[4, 4]]


def test_cross_process_parallax_sparse_wire_with_ef(tmp_path):
    """Parallax + BF16_EF across 2 real processes: the explicit shard_map
    lowering — sparse (indices, rows) wire for the embedding, bf16 error
    feedback on dense gradients — runs over a cross-process mesh and matches
    the single-process run exactly (same shard count => same rounding)."""
    single, two = _run_matrix_config(tmp_path, "parallax")
    assert two["sparse_wire_params"] == ["emb"]
    # Three dense params (wu, w2, b) carry per-replica EF residuals at dp=4.
    assert two["ef_params_dp"] == [4, 4, 4]


def test_cross_process_hierarchical_dcn_reduce(tmp_path):
    """The DCN two-phase reduce laid out the way a real pod would be: inner
    `reduce` axis within each process's devices (ICI tier), outer `data` axis
    spanning the two processes (DCN tier). Value-exact vs single-process on
    the same mesh (test_ar_knobs proves the lowering is two-phase; this
    proves it EXECUTES across a process boundary)."""
    single, two = _run_matrix_config(tmp_path, "dcn")
    assert two["mesh"]["data"] == 2 and two["mesh"]["reduce"] == 2


def test_four_process_tp_zero_mesh(tmp_path, monkeypatch):
    """The 3-tier mesh over 4 REAL processes (8 devices): model axis inside
    each process, reduce across process pairs (Adam moments ZeRO-sharded over
    the boundary), data across pair groups — coordinate arithmetic a
    2-process run cannot exercise. Value-exact vs a single-process 8-device
    run on the identical mesh."""
    monkeypatch.setenv("AUTODIST_MATRIX_PROCS", "4")
    single, two = _run_matrix_config(tmp_path, "tp_zero")
    assert two["process_count"] == 4 and two["device_count"] == 8
    assert two["mesh"]["model"] == 2 and two["mesh"]["reduce"] == 2 \
        and two["mesh"]["data"] == 2
    # The 7-row parameter lives padded to 8 on the in-process model axis.
    assert two["wu_storage_shape"] == [8, 4]
    assert two["wu_shard_shapes"] == [[4, 4]]


def test_cross_process_partitioned_allreduce(tmp_path):
    """PartitionedAR across 2 real processes: model-sharded (padded-uneven)
    parameter storage with the per-shard gradient all-reduce crossing the
    process boundary (the data axis spans the processes; the model shards
    live in-process under the canonical axis order), value-exact."""
    single, two = _run_matrix_config(tmp_path, "par")
    assert two["mesh"]["model"] == 2 and two["mesh"]["data"] == 2
    # Physical evidence: the 7-row param is padded to 8 and stored as (4, 4)
    # tiles; w2's Adam moments follow the (2, 4) model sharding.
    assert two["wu_storage_shape"] == [8, 4]
    assert two["wu_shard_shapes"] == [[4, 4]]
    assert two["w2_opt_shard_shapes"] == [[2, 4]]


def test_cross_process_powersgd(tmp_path):
    """PowerSGD's factor pmeans (P/Q low-rank wire) across 2 real processes,
    exact vs the single-process run (deterministic QR + same shard count)."""
    single, two = _run_matrix_config(tmp_path, "powersgd")
    assert two["ef_params_dp"] == []  # PowerSGDState, not EFState, carries EF
