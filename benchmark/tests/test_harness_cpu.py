"""The harness end to end on the CPU, at a tiny test-only size.

Each rehearsal is a process of its own (the device count is fixed once per
process) that calls ``run_cell(..., require_tpu=False)`` on a scratch root:
configurations, cells and a per-layer metric that exist only as new files
and new entries are found and run, which is the property later PRs rely
on. What comes back is checked for its shape and its counts; no time or
rate of a CPU run is compared with anything or written under a device
metric's name (the reported device is ``cpu``, and the metrics that need a
device trace or published peaks are absent).
"""

import json
import os
import subprocess
import sys

import pytest

from benchmark.tests import scratch
from benchmark.tests.conftest import ROOT

CHILD = """
import json, sys
sys.path.insert(0, {root!r})
from benchmark import run
line = run.run_cell({workload!r}, {seed}, {seconds}, {trace}, root={scratch!r},
                    require_tpu=False)
print(json.dumps(line))
"""

DEVICE_ONLY = {"collective_ms_per_step", "exposed_collective_pct",
               "pallas_time_pct", "pallas_roofline_pct", "device_idle_pct",
               "mfu_pct", "peak_hbm_gib"}


def _rehearse(root, workload, devices, trace, seed=0, seconds=3.0):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-c", CHILD.format(
            root=ROOT, workload=workload, seed=seed, seconds=seconds,
            trace=trace, scratch=root)],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _check_shape(line, chips):
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True, line.get("checks")
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    for metric in line["metrics"].values():
        assert set(metric) == {"value", "unit"}
        assert isinstance(metric["value"], float)
    checks = line["checks"]
    assert checks["compile_requests_in_window"] == 0
    assert checks["steps_completed"] == line["attempted"]
    assert checks["reference"]["agrees"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return scratch.make_root(tmp_path_factory.mktemp("bench_root"))


def test_one_chip_cell_end_to_end(root):
    line = _rehearse(root, "tiny-gpt-accum", devices=1, trace=False)
    _check_shape(line, 1)
    assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
    assert line["metrics"]["setup_s"]["unit"] == "s"
    assert "breakdown" not in line


def test_same_seed_same_inputs_and_weights(root):
    a = _rehearse(root, "tiny-bert-mlm", devices=1, trace=False, seed=7)
    b = _rehearse(root, "tiny-bert-mlm", devices=1, trace=False, seed=7)
    c = _rehearse(root, "tiny-bert-mlm", devices=1, trace=False, seed=8)
    key = lambda line: (line["checks"]["reference"]["reference_loss"],  # noqa: E731
                        line["checks"]["warmup_losses"])
    assert key(a) == key(b) != key(c)


def test_traced_run_reports_per_layer_metrics_and_a_new_metric(root):
    line = _rehearse(root, "tiny-bert-mlm", devices=1, trace=True)
    _check_shape(line, 1)
    names = set(line["metrics"])
    assert {"plan_build_s", "first_step_s", "cache_misses",
            "host_dispatch_ms_per_step", "window_rate_iqr_pct",
            "compiled_hbm_gib"} <= names
    assert "steps_per_boundary" in names        # found as a new file + entry
    assert line["metrics"]["steps_per_boundary"]["value"] == 2.0   # log_every
    assert not names & DEVICE_ONLY              # no device number from a CPU
    assert "tokens_per_s_per_chip" not in names
    assert "busy_s" not in line["device"]


def test_data4_cell_on_four_virtual_devices(root):
    line = _rehearse(root, "tiny-gpt-dp4", devices=4, trace=False)
    _check_shape(line, 4)
    compiled = line["checks"]["compiled"]
    assert compiled["param_device_set_sizes"] == [4]
    assert "all-reduce" in compiled["collectives"]


def test_wrong_device_count_is_a_failed_run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run(
        [sys.executable, "-c", CHILD.format(
            root=ROOT, workload="tiny-gpt-dp4", seed=0, seconds=1.0,
            trace=False, scratch=root)],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and "asks for 4" in out.stderr


def test_the_command_fails_without_an_accelerator():
    """The command as the driver runs it, here where JAX has only the CPU:
    no result line, exit code other than 0."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--workload",
         "bertl-replica-b32", "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no accelerator" in out.stderr
