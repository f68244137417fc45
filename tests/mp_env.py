"""Shared harness for the multi-process tests.

One definition of the scrub-role-env + CPU-sim-mesh + repo-on-PYTHONPATH
launch environment, used by ``strategy_matrix_mp_script.run_single_reference``
and ``seq_parallel_mp_script.run_single_reference`` — the two must stay
identical or the single-process references silently diverge from the
multi-process runs they are compared against. A reference is started in the
background (``start_single_reference``) and collected after the multi-process
run it is compared with (``alongside``): the two are independent until the
comparison. ``run_matrix_config`` / ``run_matrix_ckpt`` are the strategy
matrix's two comparisons, shared by ``test_multiprocess.py`` and
``test_multiprocess_checkpoint.py``.
"""

import contextlib
import json
import os
import subprocess
import sys
import tempfile

import numpy as np


def single_reference_env(workdir: str, device_count: int) -> dict:
    """Environment for a single-process reference subprocess: role env scrubbed
    (including a stale SYS_RESOURCE_PATH from a developer shell), CPU platform
    with ``device_count`` virtual devices, repo root prepended to PYTHONPATH,
    and ``AUTODIST_MATRIX_SINGLE=1`` so the script takes its single-process
    branch."""
    from examples.multiprocess_linear_regression import ROLE_ENV_VARS

    env = dict(os.environ)
    for k in ROLE_ENV_VARS:
        env.pop(k, None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={device_count}",
        "AUTODIST_WORKING_DIR": workdir,
        "AUTODIST_MATRIX_SINGLE": "1",
        "PYTHONPATH": repo_root() + os.pathsep + env.get("PYTHONPATH", ""),
    })
    return env


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def start_single_reference(argv, workdir: str, device_count: int) -> subprocess.Popen:
    """Start ``python *argv`` as a single-process reference and return at
    once; ``collect`` waits for it. Its output goes to unnamed files, so a
    reference that prints much never blocks on a pipe nobody reads yet."""
    logs = tempfile.TemporaryFile("w+"), tempfile.TemporaryFile("w+")
    proc = subprocess.Popen(
        [sys.executable, *argv], env=single_reference_env(workdir, device_count),
        cwd=repo_root(), stdout=logs[0], stderr=logs[1])
    proc.logs = logs
    return proc


def collect(proc: subprocess.Popen, timeout: int = 300) -> subprocess.CompletedProcess:
    """Wait for a started reference (killed at ``timeout``, as ``subprocess.run``
    does) and return what ``subprocess.run(capture_output=True)`` would."""
    try:
        proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        raise
    finally:
        captured = []
        for stream in proc.logs:
            stream.seek(0)
            captured.append(stream.read())
            stream.close()
    return subprocess.CompletedProcess(proc.args, proc.returncode, *captured)


def said(what: str, proc) -> str:
    return (f"{what} failed (rc={proc.returncode})\n"
            f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}")


@contextlib.contextmanager
def alongside(reference: subprocess.Popen, what: str):
    """The multi-process run that a started reference (``what``, for the
    message) is compared with. If the run fails, the reference is taken down
    and not waited out; otherwise it is collected here and must have exited 0."""
    try:
        yield
    except BaseException:
        reference.kill()
        collect(reference)
        raise
    proc = collect(reference)
    assert proc.returncode == 0, said(what, proc)


def run_matrix_config(tmp_path, config):
    """Run one strategy-matrix config in BOTH modes and return (single, two)."""
    import examples.multiprocess_linear_regression as mp_script
    import tests.strategy_matrix_mp_script as matrix

    single_out = tmp_path / f"{config}_single.json"
    reference = matrix.start_single_reference(
        str(single_out), config, str(tmp_path / "workdir_single"))
    two_out = tmp_path / f"{config}_two.json"
    with alongside(reference, "single-process reference"):
        proc = mp_script.run_two_process_chief(
            str(two_out), str(tmp_path / "workdir_two"), script=matrix.__file__,
            extra_args=(config,))
        assert proc.returncode == 0, said("2-process chief", proc)
    single = json.loads(single_out.read_text())
    two = json.loads(two_out.read_text())
    procs = int(os.environ.get("AUTODIST_MATRIX_PROCS", "2"))
    assert two["process_count"] == procs \
        and two["device_count"] == 2 * procs
    assert single["process_count"] == 1 \
        and single["device_count"] == 2 * procs
    # Same global mesh => the distributed run must be value-exact vs the
    # single-process reference (the reference's c0 criterion per strategy,
    # tests/integration/test_dist.py:14-42).
    np.testing.assert_allclose(two["losses"], single["losses"],
                               rtol=1e-5, atol=1e-6)
    for k in single["params"]:
        np.testing.assert_allclose(two["params"][k], single["params"][k],
                                   rtol=1e-5, atol=1e-6, err_msg=k)
    return single, two


def run_matrix_ckpt(tmp_path, monkeypatch, config):
    """The reference c10 contract against cross-process-sharded state: a
    2-process run saves (collective sharded write), DIES, a fresh 2-process
    run restores and continues — and the stitched trajectory must match an
    uninterrupted single-process run value-exactly."""
    import examples.multiprocess_linear_regression as mp_script
    import tests.strategy_matrix_mp_script as matrix

    ckpt_dir = tmp_path / "ckpt"
    ckpt_dir.mkdir()
    monkeypatch.setenv("AUTODIST_MATRIX_CKPT_DIR", str(ckpt_dir))

    straight_out = tmp_path / "straight.json"
    reference = matrix.start_single_reference(
        str(straight_out), config, str(tmp_path / "wd_straight"),
        phase="straight")
    save_out = tmp_path / "save.json"
    restore_out = tmp_path / "restore.json"
    with alongside(reference, "straight reference"):
        for phase, out in (("save", save_out), ("restore", restore_out)):
            proc = mp_script.run_two_process_chief(
                str(out), str(tmp_path / f"wd_{phase}"), script=matrix.__file__,
                extra_args=(config, f"ckpt_{phase}"))
            assert proc.returncode == 0, said(f"2-process {phase} phase", proc)

    straight = json.loads(straight_out.read_text())
    saved = json.loads(save_out.read_text())
    restored = json.loads(restore_out.read_text())
    assert saved["process_count"] == 2 and restored["process_count"] == 2

    # The checkpoint is in the sharded format (per-process shard files +
    # manifest) and no monolithic <name>-<step>.npz was ever assembled.
    # Whether BOTH processes wrote depends on the config's layout (ownership
    # dedups replicas to the lowest device id): the ZeRO test asserts it.
    files = saved["ckpt_files"]
    assert any(".shard00000-of-00002" in f for f in files), files
    assert any(f == "model-3.json" for f in files), files
    assert not any(f.endswith(".npz") and ".shard" not in f for f in files), files

    # Stitched = straight, value-exact: losses before the kill, losses after
    # the restore, and the final logical params.
    np.testing.assert_allclose(saved["losses"],
                               straight["losses"][:matrix.STEPS],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(restored["losses"],
                               straight["losses"][matrix.STEPS:],
                               rtol=1e-5, atol=1e-6)
    for k in straight["params"]:
        np.testing.assert_allclose(restored["params"][k], straight["params"][k],
                                   rtol=1e-5, atol=1e-6, err_msg=k)
    return saved, restored
