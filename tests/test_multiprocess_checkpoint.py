"""Real 2-process execution, second half: checkpoint / resume, sequence
parallelism and the documented examples.

``test_multiprocess.py`` holds the strategy matrix and says how the two-process
runs are made; the cases here were its last six, moved because one file is one
``xdist`` worker's. The helpers both halves call live in ``tests/mp_env.py``.
"""

import json

import numpy as np

import examples.multiprocess_linear_regression as mp_script
from tests.mp_env import alongside, said
from tests.mp_env import run_matrix_ckpt as _run_matrix_ckpt


def test_cross_process_checkpoint_zero_opt_state(tmp_path, monkeypatch):
    """Save/kill/restore/continue with Adam moments physically sharded along
    the process-spanning reduce axis (the state device_get cannot assemble)."""
    saved, restored = _run_matrix_ckpt(tmp_path, monkeypatch, "ps")
    # The restored run re-sharded the moments across processes again.
    assert restored["w2_opt_shard_shapes"] == [[1, 4]]
    # ZeRO moments span the process boundary, so BOTH processes wrote shards.
    assert any(".shard00001-of-00002" in f for f in saved["ckpt_files"]), \
        saved["ckpt_files"]


def test_cross_process_checkpoint_padded_uneven(tmp_path, monkeypatch):
    """Save/kill/restore/continue with the 7-row padded-to-8 parameter (and
    its Adam moments) stored model-sharded across both processes; the
    checkpoint itself holds logical (unpadded) shapes."""
    saved, restored = _run_matrix_ckpt(tmp_path, monkeypatch, "partitioned")
    assert restored["wu_storage_shape"] == [8, 4]
    assert restored["wu_shard_shapes"] == [[4, 4]]


def test_cross_process_train_loop_checkpoint_resume(tmp_path, monkeypatch):
    """training.train's own save path inside a real 2-process run: collective
    final save, then a fresh 2-process train() resumes from the latest
    checkpoint automatically and finishes — params exactly match an
    uninterrupted single-process straight run."""
    import tests.strategy_matrix_mp_script as matrix

    ckpt_dir = tmp_path / "ckpt"
    ckpt_dir.mkdir()
    monkeypatch.setenv("AUTODIST_MATRIX_CKPT_DIR", str(ckpt_dir))

    straight_out = tmp_path / "straight.json"
    reference = matrix.start_single_reference(
        str(straight_out), "ps", str(tmp_path / "wd_straight"), phase="straight")
    with alongside(reference, "straight reference"):
        for phase, out in (("train_save", tmp_path / "a.json"),
                           ("train_resume", tmp_path / "b.json")):
            proc = mp_script.run_two_process_chief(
                str(out), str(tmp_path / f"wd_{phase}"), script=matrix.__file__,
                extra_args=("ps", phase))
            assert proc.returncode == 0, said(phase, proc)

    straight = json.loads(straight_out.read_text())
    resumed = json.loads((tmp_path / "b.json").read_text())
    assert resumed["step"] == matrix.STEPS_TOTAL
    # trainloop-3 was rotated/kept and trainloop-5 exists as sharded files.
    assert any("trainloop-5" in f and ".shard" in f
               for f in resumed["ckpt_files"]), resumed["ckpt_files"]
    for k in straight["params"]:
        np.testing.assert_allclose(resumed["params"][k], straight["params"][k],
                                   rtol=1e-5, atol=1e-6, err_msg=k)


def test_cross_process_ring_attention_sequence_parallel(tmp_path):
    """Long-context across REAL processes: a 4-way seq axis spanning the
    2-process boundary, so ring attention's K/V ppermute hops cross between
    OS processes — value-exact vs the single-process run on the same mesh."""
    import tests.seq_parallel_mp_script as sp

    single_out = tmp_path / "sp_single.json"
    reference = sp.start_single_reference(str(single_out),
                                          str(tmp_path / "wd_single"))
    two_out = tmp_path / "sp_two.json"
    with alongside(reference, "single-process SP reference"):
        proc = mp_script.run_two_process_chief(
            str(two_out), str(tmp_path / "wd_two"), script=sp.__file__)
        assert proc.returncode == 0, said("2-process SP chief", proc)

    single = json.loads(single_out.read_text())
    two = json.loads(two_out.read_text())
    assert two["process_count"] == 2 and two["mesh"]["seq"] == 4
    np.testing.assert_allclose(two["losses"], single["losses"],
                               rtol=1e-5, atol=1e-6)
    for k in single["params_sample"]:
        np.testing.assert_allclose(two["params_sample"][k],
                                   single["params_sample"][k],
                                   rtol=1e-5, atol=1e-6, err_msg=k)


def test_async_ps_example_runs(tmp_path):
    """The documented async-PS example (examples/async_ps_train.py) runs
    end-to-end: 2 processes, all updates applied, wire accounting reported."""
    import os

    script = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          "examples", "async_ps_train.py")
    out = tmp_path / "example_summary.json"
    proc = mp_script.run_two_process_chief(
        str(out), str(tmp_path / "workdir"), script=script,
        extra_args=("--steps", "4", "--out", str(out)))
    assert proc.returncode == 0, (
        f"example failed (rc={proc.returncode})\n"
        f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}")
    summary = json.loads(out.read_text())
    assert summary["applied_updates"] == 8  # 4 chief + 4 worker
    assert summary["worker_wire_received_bytes"] > 0


def test_auto_wired_cross_process_async_ps(tmp_path):
    """The public API alone (2-node spec + PS(staleness)) wires the whole async
    protocol: worker launch, transport address shipping, chief-side serving,
    worker-side remote stepping — no manual plumbing in the user script."""
    import os

    import tests.auto_async_script as aas

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "auto_async_script.py")
    out = tmp_path / "auto_async.json"
    proc = mp_script.run_two_process_chief(
        str(out), str(tmp_path / "workdir"), script=script)
    assert proc.returncode == 0, (
        f"chief failed (rc={proc.returncode})\n"
        f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}")
    result = json.loads(out.read_text())

    assert result["num_worker_slots"] == 2
    # Every step from BOTH processes was applied by the chief's service.
    assert result["final_version"] == result["chief_steps"] + result["worker_steps"]
    assert result["chief_losses"][-1] < result["chief_losses"][0]
    assert np.isfinite(result["w"]) and result["w"] != 0.0
