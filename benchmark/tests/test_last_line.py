"""The last line's shape, and BENCHMARK.json against the files it names."""

import json
import os
import re

import pytest

from benchmark import harness, peaks
from benchmark.tests.conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_names_files_that_exist(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.traffic["chips"] == w["chips"]
        assert len(w["why"]) <= 200
        cell.find("families", cell.config["family"] + ".py")
        cell.find("reference", cell.config["family"] + ".py")
        cell.find("jobs", cell.traffic["job"] + ".py")
        for m in cell.per_layer:
            assert callable(cell.load_module("layers", m["name"]).read)
        assert {m["name"] for m in cell.end_to_end} == \
            {"tokens_per_s_per_chip", "setup_s"}
    for entry in bench["configs"]:
        assert entry["reduced"] == []
        with open(os.path.join(ROOT, entry["file"])) as f:
            config = json.load(f)
        assert config["reduced"] == [] and config["departures"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert all(m["moves"] in e2e for m in bench["per_layer"])
    assert all(m["bound"] <= 0.1 for m in bench["end_to_end"])


def _record(cell, **over):
    record = {
        "cell": cell, "peaks": peaks.peaks_for("TPU v5 lite"),
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 15 * 2**30},
        "correct": True, "attempted": 20, "failed": 0, "checks": {},
        "end_to_end": {"tokens_per_s_per_chip": 40000.0, "setup_s": 30.0},
        "tokens_per_s_per_chip": 40000.0, "train_flops_per_token": 2.0e9,
        "mean_rate": 39000.0,
        "period_rates": [40000.0, 40400.0, 40800.0, 41200.0, 41600.0],
        "plan_build_s": 3.0, "first_step_s": 5.0, "cache_misses": 0,
        "compiled": {"compiled_bytes": {"argument": 4 * 2**30, "temp": 9 * 2**30,
                                        "output": 4 * 2**30, "alias": 4 * 2**30}},
        "dispatch_spans_ms": [1.0, 2.0, 30.0],
    }
    record.update(over)
    return record


def test_untraced_line_carries_the_end_to_end_metrics():
    cell = harness.load_cell("gpt2m-pretrain-1k")
    line = harness.result_line(cell, _record(cell), trace=False)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert line["metrics"] == {
        "tokens_per_s_per_chip": {"value": 40000.0, "unit": "tokens/s"},
        "setup_s": {"value": 30.0, "unit": "s"}}
    json.dumps(line)


def test_traced_line_leaves_out_what_its_reader_cannot_read():
    cell = harness.load_cell("gpt2m-pretrain-1k")
    line = harness.result_line(cell, _record(cell, breakdown={
        "device_ops": [["fusion", 1.0]], "idle_gaps": [["no span", 0.1]]}),
        trace=True)
    m = line["metrics"]
    assert m["mfu_pct"]["value"] == pytest.approx(100 * 2.0e9 * 40000 / 197e12)
    assert m["peak_hbm_gib"]["value"] == 15.0
    assert m["compiled_hbm_gib"]["value"] == 13.0
    assert m["host_dispatch_ms_per_step"]["value"] == 2.0     # the median
    assert m["window_rate_iqr_pct"]["value"] == pytest.approx(100 * 800 / 40800)
    assert m["window_mean_vs_quoted_pct"]["value"] == pytest.approx(97.5)
    assert m["window_slow_periods_pct"]["value"] == 0.0
    slow = harness.result_line(cell, _record(cell, period_rates=[
        40000.0, 39990.0, 35000.0, 39000.0]), trace=True)["metrics"]
    assert slow["window_slow_periods_pct"]["value"] == 50.0
    # no trace in this record: the device-trace readers return nothing
    assert not {"device_idle_pct", "pallas_time_pct", "collective_ms_per_step",
                "exposed_collective_pct", "pallas_roofline_pct"} & set(m)
    assert line["breakdown"]["device_ops"] == [["fusion", 1.0]]


def test_a_missing_end_to_end_metric_is_an_error():
    cell = harness.load_cell("bertl-replica-b32")
    with pytest.raises(harness.BenchmarkError):
        harness.result_line(cell, _record(cell, end_to_end={"setup_s": 1.0}),
                            trace=False)


@pytest.mark.parametrize("step_seconds, clean", [
    # the run of gpt2m-pretrain-1k that stalled in its last period (my chip
    # run, PR 22: the loop's own log, seconds a period of two steps, halved)
    ([1.4845, 1.4840, 1.4840, 1.4845, 1.4845, 1.6760], 1.4841),
    # a stall of that size in every second period, which is what the
    # driver's first check of PR 22 read in two runs of six: the median of
    # these is 6% high
    ([1.484, 1.679, 1.484, 1.679, 1.484, 1.679, 1.484, 1.679], 1.484),
    # two thirds of eighteen periods stalled by various amounts
    ([1.484] * 6 + [1.5, 1.55, 1.6, 1.65, 1.7, 1.75] * 2, 1.484),
    ([0.0529] * 17, 0.0529),
    ([0.4002], 0.4002),
])
def test_the_quoted_step_time_holds_under_one_sided_stalls(step_seconds, clean):
    from benchmark.jobs import train
    assert train.undisturbed_step_seconds(step_seconds) == \
        pytest.approx(clean, rel=2e-4)


def test_the_quoted_step_time_moves_when_every_period_does():
    from benchmark.jobs import train
    base = [1.484, 1.485, 1.484, 1.679, 1.484, 1.484, 1.485, 1.484]
    slower = [1.02 * s for s in base]
    assert train.undisturbed_step_seconds(slower) == \
        pytest.approx(1.02 * train.undisturbed_step_seconds(base))
