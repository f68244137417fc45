"""The five readers of the program's HBM account (``benchmark/layers/hbm_*``
over ``benchmark/hbm_account.py``): each on a registry filled by hand with a
chip's readings, and None from every one where the program read no
allocator, which is what a CPU rehearsal leaves behind."""

import json

import pytest

from benchmark import harness, hbm_account
from benchmark.layers import (hbm_headroom_gib, hbm_kept_gib,
                              hbm_resident_gib, hbm_step_extra_gib,
                              hbm_unowned_gib)
from benchmark.tests import scratch
from benchmark.tests.conftest import ROOT
from benchmark.tests.test_harness_cpu import _check_shape, _rehearse

GIB = 2 ** 30
READERS = {
    "hbm_resident_gib": hbm_resident_gib, "hbm_unowned_gib": hbm_unowned_gib,
    "hbm_step_extra_gib": hbm_step_extra_gib, "hbm_kept_gib": hbm_kept_gib,
    "hbm_headroom_gib": hbm_headroom_gib}
# A chip of 15.75 GiB: 9 held at the boundary, 3 of them the state; the step
# takes the state and 0.25 of batch, 4 of temporaries, and writes the new
# state into the old one's buffers.
BY_HAND = {
    "train.hbm.resident_bytes": 9 * GIB, "train.hbm.limit_bytes": 15.75 * GIB,
    "train.hbm.state_bytes": 3 * GIB, "train.hbm.unowned_bytes": 6 * GIB,
    "train.hbm.allocator_peak_bytes": 14 * GIB,
    "train.hbm.allocator_peak_rise_bytes": 0,
    "train.hbm.predicted_bytes": 13.5 * GIB,
    "train.hbm.headroom_bytes": 2.25 * GIB,
    "step.hbm.argument_bytes": 3.25 * GIB, "step.hbm.temp_bytes": 4 * GIB,
    "step.hbm.output_bytes": 3.25 * GIB, "step.hbm.alias_bytes": 3 * GIB,
    "step.hbm.code_bytes": 0.01 * GIB, "step.hbm.kept_bytes": 1.5 * GIB}
EXPECTED = {"hbm_resident_gib": 9.0, "hbm_unowned_gib": 6.0,
            "hbm_step_extra_gib": 4.25, "hbm_kept_gib": 1.5,
            "hbm_headroom_gib": 2.25}


@pytest.fixture
def registry(monkeypatch):
    from autodist_tpu.telemetry import metrics
    fresh = metrics.Registry()
    monkeypatch.setattr(metrics, "_REGISTRY", fresh)
    return fresh


def _fill(registry, gauges):
    for name, value in gauges.items():
        registry.gauge(name).set(int(value))
    registry.counter("step.hbm.account_s").inc(0.004)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_a_registry_filled_by_hand(registry, name, capsys):
    _fill(registry, BY_HAND)
    assert READERS[name].read({}) == pytest.approx(EXPECTED[name])
    said = capsys.readouterr().err
    if name == "hbm_headroom_gib":
        assert "train.hbm.predicted_bytes 13.500 GiB" in said
    if name == "hbm_resident_gib":
        assert "train.hbm.allocator_peak_rise_bytes 0.000 GiB" in said
        assert "train.hbm.state_bytes 3.000 GiB" in said
    if name == "hbm_step_extra_gib":
        assert "step.hbm.argument_bytes 3.250 GiB" in said


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_nothing_where_no_allocator_was_read(registry, name):
    """The compiler's count alone (booked on any backend, the CPU too) is no
    chip's reading: without ``train.hbm.resident_bytes`` every reader is
    silent, and so is one whose own gauge is missing beside it."""
    assert READERS[name].read({}) is None                # an older program
    _fill(registry, {k: v for k, v in BY_HAND.items()
                     if k.startswith("step.hbm.")})
    assert READERS[name].read({}) is None                # a CPU rehearsal
    assert not hbm_account.booked()
    registry.gauge("train.hbm.resident_bytes").set(9 * GIB)
    if name in ("hbm_resident_gib", "hbm_step_extra_gib", "hbm_kept_gib"):
        assert READERS[name].read({}) is not None
    else:
        assert READERS[name].read({}) is None            # its gauge is missing


def test_the_five_are_entries_of_the_device_layer():
    with open(f"{ROOT}/BENCHMARK.json") as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"] if m["name"] in READERS}
    assert set(entries) == set(READERS)
    assert [m["name"] for m in bench["per_layer"]][-5:] == [
        "hbm_resident_gib", "hbm_unowned_gib", "hbm_step_extra_gib",
        "hbm_kept_gib", "hbm_headroom_gib"]              # appended, in order
    for name, m in entries.items():
        assert (m["unit"], m["source"], m["layer"], m["moves"]) == (
            "GiB", "program_counter", "device", "tokens_per_s_per_chip")
        assert m["better"] == ("higher" if name == "hbm_headroom_gib"
                               else "lower")
    # Only the cells whose configuration checkpoints its layers keep named
    # values: the others' line leaves the metric out, so it lists its cells.
    remat = set()
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"], ROOT)
        if cell.config.get("assumed", {}).get("remat") is True:
            remat.add(w["name"])
    assert set(entries["hbm_kept_gib"]["workloads"]) == remat
    assert all("workloads" not in m for name, m in entries.items()
               if name != "hbm_kept_gib")


def test_cpu_rehearsal_reports_none_of_them(tmp_path):
    """The harness finds the five in a traced run, the program books the
    compiler's count of the step, and no line carries a chip's number."""
    root = scratch.make_root(tmp_path)
    line = _rehearse(root, "tiny-bert-mlm", devices=1, trace=True)
    _check_shape(line, 1)
    assert not set(line["metrics"]) & set(READERS)
    assert "compiled_hbm_gib" in line["metrics"]
