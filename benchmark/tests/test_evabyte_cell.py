"""What PR 50 added, by new files only: EvaByte's required operations and its
kernels' operations and bytes against counts made by hand, the parameter
count of the cut, the four new readers on a trace made by hand,
``BENCHMARK.json``'s new entries, and a tiny ``evabyte`` cell end to end on
the CPU through ``run_cell(require_tpu=False)``."""

import json
import os
import types

import pytest

from benchmark import flops_evabyte, harness, kernel_parts, peaks, program_counters
from benchmark.layers import (eva_bwd_roofline_pct, eva_fwd_roofline_pct,
                              eva_tile_fill_pct, eva_time_pct)
from benchmark.tests import scratch, test_harness_cpu
from benchmark.tests.conftest import ROOT

V5E = peaks.peaks_for("TPU v5 lite")
CELL = "evabyte-pretrain-16k"
CONFIG = "evabyte-6.5b"
READERS = {"eva_fwd_roofline_pct": eva_fwd_roofline_pct,
           "eva_bwd_roofline_pct": eva_bwd_roofline_pct,
           "eva_time_pct": eva_time_pct, "eva_tile_fill_pct": eva_tile_fill_pct}
CUT = ["num_hidden_layers", "num_attention_heads", "num_key_value_heads"]


def _cell():
    return harness.load_cell(CELL, ROOT)


# ------------------------------------------------------------ required work

def test_evabyte_train_flops_per_token_by_hand():
    c = _cell().config
    assert (c["hidden_size"], c["num_attention_heads"], c["layer_heads"],
            c["head_dim"], c["intermediate_size"], c["window_size"],
            c["chunk_size"], c["num_pred_heads"], c["vocab_size"],
            c["num_hidden_layers"]) \
        == (4096, 8, 32, 128, 11008, 2048, 16, 8, 320, 4)
    # a query sees its window's keys up to itself and 128 summaries of each
    # earlier window: 8 windows of 2,048
    own = 8 * 2048 * 2049 // 2
    earlier = 2048 * 128 * (0 + 1 + 2 + 3 + 4 + 5 + 6 + 7)
    assert (own, earlier) == (16_785_408, 7_340_032)
    assert flops_evabyte.visible_pairs(16_384, 2048, 16) == (own, earlier)
    pairs = own + earlier
    # operations a token, forward (a multiply-add is two)
    projections = 2 * 4 * 4096 * 1024                     # q, k, v, o at 8 heads
    mlp = 3 * 2 * 4096 * 11_008
    eva = 2 * 2 * pairs * 1024 / 16_384                   # two products a pair
    head = 2 * 4096 * 8 * 320                             # the eight heads once
    by_part = flops_evabyte.forward_flops_per_token(flops_evabyte.shape(c), 16_384)
    assert by_part == {"projections": 4 * projections, "eva": 4 * eva,
                       "mlp": 4 * mlp, "head": head}
    forward = 4 * (projections + mlp + eva) + head
    assert flops_evabyte.train_flops_per_token(c, 16_384) == 3 * forward
    # a layer's forward at 16,384 positions: 4.98 TFLOP of projections and
    # MLP, 0.099 of EVA (2.0% of the layer); the MLP 89% of the layer
    layer = (projections + mlp + eva) * 16_384
    assert (projections + mlp) * 16_384 == pytest.approx(4.98e12, rel=2e-3)
    assert eva * 16_384 == pytest.approx(0.0988e12, rel=2e-3)
    assert round(100 * eva * 16_384 / layer, 1) == 1.9
    assert round(100 * mlp * 16_384 / layer) == 87
    # the step: 16,384 tokens x 3.78 GFLOP = 62.0 TFLOP required
    assert 3 * forward * 16_384 == pytest.approx(62.0e12, rel=1e-3)


def test_kernel_costs_by_hand_and_the_parts_sum_to_the_step():
    cell = _cell()
    parts = flops_evabyte.parts(cell.config, cell.traffic)
    assert set(parts) == {"eva_fwd", "eva_bwd"}
    fwd, bwd = flops_evabyte.eva_cost(batch=1, seq_len=16_384, heads=8,
                                      head_dim=128, window=2048, chunk=16)
    pairs = 24_125_440
    # forward 2 products, backward 5, each pairs x 128 multiply-adds a head
    assert fwd.flops == 2 * 2 * 8 * pairs * 128
    assert bwd.flops == 5 * 2 * 8 * pairs * 128
    tensor = 16_384 * 8 * 128 * 2
    summary = tensor // 16
    assert fwd.hbm_bytes == 4 * tensor + 2 * summary          # q, k, v, o; k~, v~
    # q, k, v, o, dO, dq, dk, dv; k~, v~ and their float32 gradients
    assert bwd.hbm_bytes == 8 * tensor + 2 * summary + 2 * 2 * summary
    assert fwd.bound(V5E) == bwd.bound(V5E) == "compute"
    assert fwd.least_seconds(V5E) == pytest.approx(0.5016e-3, rel=1e-3)
    assert bwd.least_seconds(V5E) == pytest.approx(1.2540e-3, rel=1e-3)
    assert parts["eva_fwd"].flops == 4 * fwd.flops
    assert parts["eva_bwd"].hbm_bytes == 4 * bwd.hbm_bytes
    whole = flops_evabyte.kernel_cost_per_step(cell.config, cell.traffic)
    assert whole.flops == pytest.approx(sum(p.flops for p in parts.values()))
    assert whole.hbm_bytes == pytest.approx(
        sum(p.hbm_bytes for p in parts.values()))
    # the forward's share of the stack's required work is its two products
    by_part = flops_evabyte.forward_flops_per_token(
        flops_evabyte.shape(cell.config), 16_384)
    assert parts["eva_fwd"].flops == pytest.approx(by_part["eva"] * 16_384)


def test_the_cut_has_the_parameters_the_configuration_file_counts():
    import jax
    import numpy as np
    cell = _cell()
    family = cell.load_module("families", "evabyte")
    built = family.build(cell.config, dict(cell.traffic, pool_batches=1), 0, 1,
                         abstract=True)
    attention = 4 * 4096 * 1024 + 2 * 8 * 128             # q, k, v, o; phi, mu
    mlp = 3 * 4096 * 11_008
    layer = attention + mlp + 2 * 4096
    assert layer == 152_053_760
    total = 4 * layer + 320 * 4096 + 4096 * 8 * 320 + 4096
    assert total == 620_015_616
    leaves = jax.tree_util.tree_leaves_with_path(built.params)
    assert sum(int(np.prod(x.shape)) for _, x in leaves) == total
    assert {str(x.dtype) for _, x in leaves} == {"float32"}
    assert "620,015,616" in cell.config["reduced_why"]
    # 20 bytes a parameter on the chip (PERF.md §4): 11.55 GiB of 15.75
    assert 20 * total / 2**30 == pytest.approx(11.55, abs=0.005)
    assert 0.25 * V5E.hbm_bytes < 20 * total < V5E.hbm_bytes
    # four whole layers: 821M parameters, 15.30 GiB at 20 B
    whole = 4 * (4 * 4096 * 4096 + 2 * 32 * 128 + mlp + 2 * 4096) \
        + total - 4 * layer
    assert whole == 821_366_784
    assert 20 * whole / 2**30 == pytest.approx(15.30, abs=0.005)
    assert built.pool[0]["tokens"].shape == (1, 16_385)
    assert 0 <= built.pool[0]["tokens"].min() and \
        built.pool[0]["tokens"].max() < 320


# ------------------------------------------------------------- the readers

def _record(by_group, busy_s=1.0, steps=2, cell=None):
    device = types.SimpleNamespace(by_group=by_group, busy_s=busy_s)
    trace = types.SimpleNamespace(devices={0: device})
    return {"trace": trace, "trace_steps": steps, "peaks": V5E,
            "cell": cell or _cell()}


def test_new_readers_on_a_trace_made_by_hand(monkeypatch):
    record = _record({"pallas:eva_fwd": 0.016, "pallas:eva_bwd": 0.040,
                      "fusion (kOutput)": 0.4},
                     busy_s=2.0)
    # 2 steps of 4 layers need 8 x 0.5016 ms of forward and took 16 ms;
    # 8 x 1.254 ms of backward and took 40
    assert eva_fwd_roofline_pct.read(record) == pytest.approx(25.08, rel=1e-3)
    assert eva_bwd_roofline_pct.read(record) == pytest.approx(25.08, rel=1e-3)
    # the two kernels' 56 ms of 2 s busy
    assert eva_time_pct.read(record) == pytest.approx(2.8)
    # the tiles of 4 layers x 8 heads x one sequence hold 28,311,552 pairs a
    # head for the 24,125,440 the mask keeps
    monkeypatch.setattr(program_counters, "value", lambda name: {
        "eva.pairs.computed": 32 * 28_311_552}.get(name))
    assert eva_tile_fill_pct.read(record) == pytest.approx(85.21, rel=1e-3)
    for reader in READERS.values():
        assert 0 < reader.read(record) <= 100


def test_new_readers_find_nothing_where_there_is_nothing_to_read(monkeypatch):
    # another family's cell, a run without a device trace, a program older
    # than the kernels' names or without the gauge: nothing, and no raise
    groups = {"pallas:flash_fwd": 0.3, "pallas:eva_fwd": 0.1}
    untraced = {"trace": None, "cell": _cell(), "peaks": V5E, "trace_steps": 4}
    for other in ("gpt2m-pretrain-1k", "kanana-pretrain-16k",
                  "nemotron-pretrain-8k"):
        record = _record(groups, cell=harness.load_cell(other, ROOT))
        for reader in READERS.values():
            assert reader.read(record) is None
    for name, reader in READERS.items():
        if name != "eva_tile_fill_pct":         # a counter, not the trace's
            assert reader.read(untraced) is None
    monkeypatch.setattr(program_counters, "value", lambda name: None)
    assert eva_tile_fill_pct.read(_record(groups)) is None
    names = tuple(n for n in kernel_parts.program_kernel_names()
                  if not n.startswith("eva_"))
    for parent_names in (None, names):
        monkeypatch.setattr(kernel_parts, "program_kernel_names",
                            lambda names=parent_names: names)
        for name, reader in READERS.items():
            assert reader.read(_record(groups)) is None


def test_named_kernels_missing_from_the_trace_fail_the_run():
    for name in ("eva_fwd_roofline_pct", "eva_bwd_roofline_pct"):
        with pytest.raises(harness.BenchmarkError, match="no time under"):
            READERS[name].read(_record({"pallas:jvp__": 0.2}))


# ------------------------------------------------------ BENCHMARK.json

def test_new_entries_name_files_that_exist_and_cut_what_the_issue_names():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by name, not by place: later PRs append theirs
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert work == {"name": CELL, "config": CONFIG, "traffic": "bytes-16k",
                    "chips": 1, "why": work["why"]}
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == CUT
    assert entry["source"] == ("https://huggingface.co/EvaByte/EvaByte/blob/"
                               "main/config.json")
    assert all(1 <= len(x[k]) <= 200 for x in (entry, work)
               for k in ("why", "source") if k in x)
    cell = _cell()
    for sub in ("families", "reference"):
        cell.find(sub, "evabyte.py")
    new = [m for m in bench["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in new] == list(READERS)
    for m in new:
        assert m["workloads"] == [CELL] and m["layer"] == "kernels"
        assert m["unit"] == "%" and m["moves"] == "tokens_per_s_per_chip"
        assert m["source"] == ("program_counter" if "tile_fill" in m["name"]
                               else "device_trace")
        assert m["better"] == ("lower" if m["name"].endswith("time_pct") else "higher")
        assert callable(cell.load_module("layers", m["name"]).read)
    # one cell in four may take four chips: 3 of 13, and this one takes one
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    if len(bench["workloads"]) == 13:
        assert four == 3
    assert [w["name"] for w in bench["workloads"]
            if w["traffic"] == "bytes-16k"] == [CELL]
    t = cell.traffic
    assert (t["seq_len"], t["micro_batch"], t["accumulation"], t["pool_batches"],
            t["check_sequences"], t["strategy"], t["mesh"], t["job"]) \
        == (16_384, 1, 1, 8, 1, "AllReduce", {"data": 1}, "train")
    # every metric without a list of cells applies to the new cell too
    assert {m["name"] for m in cell.per_layer} >= {
        m["name"] for m in bench["per_layer"] if "workloads" not in m}


def test_the_configuration_keeps_every_published_number_but_the_cut():
    """Against the catalog's own ``config`` where the guide is installed; the
    cut, the deployment and every assumed fact are stated in the file."""
    config = _cell().config
    cut = {"num_hidden_layers": 4, "num_attention_heads": 8,
           "num_key_value_heads": 8}
    for key, value in cut.items():
        assert config[key] == value
    assert config["published"] == {"num_hidden_layers": 32,
                                   "num_attention_heads": 32,
                                   "num_key_value_heads": 32}
    assert [r.split()[0] for r in config["reduced"]] == CUT
    widths = dict(hidden_size=4096, intermediate_size=11008, vocab_size=320,
                  window_size=2048, chunk_size=16, num_pred_heads=8,
                  rope_theta=100000, rms_norm_eps=1e-5, init_std=0.01275,
                  max_seq_length=32768, max_position_embeddings=32768,
                  attention_class="eva", model_type="evabyte",
                  norm_add_unit_offset=True, fp32_logits=True,
                  fp32_skip_add=True, tie_word_embeddings=False)
    for key, value in widths.items():
        assert config[key] == value, key
    assert config["family"] == "evabyte"
    assert (config["layer_heads"], config["first_head_held"],
            config["head_dim"]) == (32, 0, 128)
    assert "4 chips" in config["deployment"] and "heads" in config["deployment"]
    assumed = config["assumed"]
    assert (assumed["attention_impl"], assumed["fused_head"], assumed["remat"],
            assumed["optimizer"]) == ("kernel", False, True, "adamw")
    assert set(assumed) == {k for keys in config["assumed_why"]
                            for k in keys.split(", ")}
    assert config["departures"] and config["expects_pallas"] is True
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "EvaByte")
    assert row["source_url"] in config["source"]
    for key, value in row["config"].items():
        if key not in cut:
            assert config[key] == value, key
        else:
            assert config["published"][key] == value, key


# ----------------------------------------------------------- CPU rehearsal

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The shared scratch root plus a tiny EvaByte configuration and cell, as
    new files and entries: two layers, 2 of 4 heads of 16 held, windows of
    32 in chunks of 4, three prediction heads."""
    root = scratch.make_root(tmp_path_factory.mktemp("evabyte_root"))
    with open(os.path.join(ROOT, "benchmark", "configs", f"{CONFIG}.json")) as f:
        config = json.load(f)
    config.update(hidden_size=64, num_attention_heads=2, num_key_value_heads=2,
                  layer_heads=4, first_head_held=2, head_dim=16,
                  intermediate_size=96, num_hidden_layers=2, window_size=32,
                  chunk_size=4, num_pred_heads=3, vocab_size=40,
                  max_seq_length=256, init_std=0.05)
    config["assumed"] = dict(config["assumed"], learning_rate=0.003,
                             warmup_steps=4)
    with open(os.path.join(root, "extra", "configs", "tiny-evabyte.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(ROOT, "benchmark", "traffic", "bytes-16k.json")) as f:
        traffic = json.load(f)
    traffic.update(seq_len=96, micro_batch=2, check_sequences=2)
    with open(os.path.join(root, "extra", "traffic", "tiny-bytes.json"), "w") as f:
        json.dump(traffic, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-evabyte", "source": "test only",
                             "reduced": [], "why": "test only",
                             "file": "extra/configs/tiny-evabyte.json"})
    bench["workloads"].append({"name": "tiny-evabyte-bytes",
                               "config": "tiny-evabyte", "traffic": "tiny-bytes",
                               "chips": 1, "why": "test only"})
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_tiny_evabyte_cell_end_to_end_on_the_cpu(root, trace):
    line = test_harness_cpu._rehearse(root, "tiny-evabyte-bytes", devices=1,
                                      trace=trace, seconds=4.0)
    test_harness_cpu._check_shape(line, 1)
    reference = line["checks"]["reference"]
    assert reference["loss_rel_diff"] < 1e-3 and reference["grad_rel_l2"] < 3e-2
    if trace:
        # no device trace on the CPU: the trace's readers give nothing; the
        # program's own count of its tiles is there
        assert set(line["metrics"]) & set(READERS) == {"eva_tile_fill_pct"}
        assert 0 < line["metrics"]["eva_tile_fill_pct"]["value"] <= 100
        assert "compiled_hbm_gib" in line["metrics"]
    else:
        assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
