"""chip_smoke.py's phases, rehearsed in-process on the CPU mesh at a tiny
width, and the rules this bring-up put behind it: no fallback that hides the
device, a compile cache placed from outside."""

import json
import os
import sys
import types

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402  (repo root, not a package)
from autodist_tpu.utils import compile_cache  # noqa: E402


def _lines(capsys):
    return [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]


def test_tiny_rehearsal_passes_and_last_line_is_the_result(capsys):
    assert chip_smoke.run(chip_smoke.TINY, require_tpu=False) == 0
    lines = _lines(capsys)
    assert [r.get("phase") for r in lines[:-1]] == [
        "device", "native", "train", "kernels", "cache"]
    # Exactly the keys the driver reads, nothing else in that line.
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    train = lines[2]
    assert train["losses"][-1] < train["losses"][0]
    assert train["train_loop_losses"]
    # The runner's cost probe assumes re-lowering after the first dispatch
    # hits jit's executable cache: far under the compile it would repeat.
    assert train["relower_after_dispatch_s"] < train["compile_and_first_step_s"] / 4
    assert max(lines[3]["rel_err"].values()) <= chip_smoke.KERNEL_TOL


def test_sharded_rehearsal_passes_on_the_virtual_mesh(capsys):
    chips = len(jax.devices())
    assert chip_smoke.run(chip_smoke.TINY, chips=chips, require_tpu=False) == 0
    lines = _lines(capsys)
    # Only the sharded path and what it is compared with.
    assert {r["phase"] for r in lines[:-1]} == {"device", "sharded"}
    cases = [r for r in lines if r.get("phase") == "sharded"]
    assert [c["case"].split()[0] for c in cases] == [
        "single_device", "AllReduce", "PartitionedPS"]
    for case in cases[1:]:
        assert case["param_device_set_sizes"] == [chips]
        assert "all-reduce" in case["collectives"]
    assert lines[-1]["ok"] is True and lines[-1]["device"]["count"] == chips


def test_failed_phase_gives_nonzero_exit_and_ok_false(capsys, monkeypatch):
    def broken(size):
        raise chip_smoke.SmokeFailure("kernels disagree")

    monkeypatch.setattr(chip_smoke, "phase_train", lambda size, require_tpu: {})
    monkeypatch.setattr(chip_smoke, "phase_kernels", broken)
    monkeypatch.setattr(chip_smoke, "phase_cache", pytest.fail)  # never reached
    assert chip_smoke.run(chip_smoke.TINY, require_tpu=False) == 1
    last = _lines(capsys)[-1]
    assert last["ok"] is False and last["phase"] == "kernels"
    assert "kernels disagree" in last["error"]


def test_require_tpu_on_cpu_fails_instead_of_falling_back(capsys, monkeypatch):
    monkeypatch.setattr(chip_smoke, "phase_train", pytest.fail)  # never reached
    assert chip_smoke.run(chip_smoke.TINY, require_tpu=True) == 1
    lines = _lines(capsys)
    assert len(lines) == 1 and lines[0]["ok"] is False
    assert lines[0]["phase"] == "device" and "no TPU" in lines[0]["error"]


def test_use_interpret_raises_on_an_unknown_backend(monkeypatch):
    from autodist_tpu.ops import mosaic_compiles
    from autodist_tpu.ops.flash_attention import _use_interpret

    assert _use_interpret() is True          # the CPU test mesh interprets
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _use_interpret() is False and mosaic_compiles()
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        _use_interpret()


def test_cache_dir_is_placed_from_outside_or_fixed_in_the_checkout(tmp_path):
    # Set from outside: JAX reads the variable itself, the program sets nothing.
    assert compile_cache.cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": "/some/dir"}, str(tmp_path)) is None
    # Unset: one fixed path inside the checkout, whatever the pid or the time.
    want = os.path.join(str(tmp_path), ".jax_cache")
    assert compile_cache.cache_dir({}, str(tmp_path)) == want
    assert compile_cache.cache_dir({"JAX_COMPILATION_CACHE_DIR": ""},
                                   str(tmp_path)) == want
    assert os.path.isfile(os.path.join(compile_cache.CHECKOUT_ROOT,
                                       "chip_smoke.py"))
    with open(os.path.join(compile_cache.CHECKOUT_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cache_helper_sets_nothing_on_the_cpu_backend():
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    assert compile_cache.configure() is None
    assert (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs) == before


def test_peak_spec_never_reports_a_device_source_with_empty_peaks(monkeypatch):
    from autodist_tpu.telemetry import profiling
    monkeypatch.delenv("AUTODIST_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("AUTODIST_PEAK_MEMBW", raising=False)
    known = profiling.peak_spec(types.SimpleNamespace(
        platform="tpu", device_kind="TPU v5 lite"))
    assert known.source == "device:TPU v5 lite"
    assert known.flops_per_s == 197e12 and known.membw_bytes_per_s == 819e9
    unknown = profiling.peak_spec(types.SimpleNamespace(
        platform="tpu", device_kind="TPU v99"))
    assert unknown.source == "unknown:TPU v99"
    assert unknown.flops_per_s is None and unknown.membw_bytes_per_s is None
