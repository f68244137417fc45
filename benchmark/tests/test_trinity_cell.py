"""What PR 29 added, by new files only: Trinity-Mini's required operations
against counts made by hand, the parameter count of the cut, the four new
readers on a trace made by hand, ``BENCHMARK.json``'s new entries, and a tiny
``afmoe`` cell end to end on the CPU through ``run_cell(require_tpu=False)``."""

import json
import os
import types

import pytest

from benchmark import flops, flops_afmoe, flops_moe, harness, kernel_parts, peaks
from benchmark.layers import (moe_held_gmm_roofline_pct,
                              window_flash_bwd_roofline_pct,
                              window_flash_fwd_roofline_pct,
                              window_flash_time_pct)
from benchmark.tests import scratch, test_harness_cpu
from benchmark.tests.conftest import ROOT

V5E = peaks.peaks_for("TPU v5 lite")
CELL = "trinity-pretrain-8k"
READERS = {"window_flash_fwd_roofline_pct": window_flash_fwd_roofline_pct,
           "window_flash_bwd_roofline_pct": window_flash_bwd_roofline_pct,
           "window_flash_time_pct": window_flash_time_pct,
           "moe_held_gmm_roofline_pct": moe_held_gmm_roofline_pct}


def _cell():
    return harness.load_cell(CELL, ROOT)


# ------------------------------------------------------------ required work

def test_band_pairs_by_hand():
    # 8 positions, window 3: 1 + 2 + 3 x 6; no window: the triangle
    assert flops_afmoe.band_pairs(8, 3) == 1 + 2 + 3 * 6
    assert flops_afmoe.band_pairs(8, None) == 36 == flops_afmoe.band_pairs(8, 100)
    # the cell's sliding layer: 1,792.125 keys a query where a causal one sees 4,096.5
    assert flops_afmoe.band_pairs(8192, 2048) == 2048 * 2049 // 2 + 6144 * 2048
    assert flops_afmoe.band_pairs(8192, 2048) / 8192 == 1792.125
    assert flops_afmoe.band_pairs(8192, None) / 8192 == 4096.5


def test_trinity_train_flops_per_token_by_hand():
    cell = _cell()
    c = cell.config
    assert (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["intermediate_size"], c["moe_intermediate_size"],
            c["router_width"], c["num_experts"], c["num_experts_per_tok"],
            c["sliding_window"], c["vocab_size"], c["num_hidden_layers"]) \
        == (2048, 32, 4, 128, 6144, 1024, 128, 8, 8, 2048, 25024, 5)
    # multiply-adds a token, forward
    projections = 5 * 2048 * (3 * 4096 + 2 * 512)        # q, gate, o; k, v
    attention = 2 * 4096 * (4 * 1792.125 + 4096.5)       # q.k^T and p.v, 32 heads of 128
    dense_mlp = 3 * 2048 * 6144
    router = 4 * 2048 * 128
    shared = 4 * 3 * 2048 * 1024
    held = 4 * 3 * 2048 * 1024 * (8 * 8 / 128)           # half a routed row a token
    head = 2048 * 25024
    assert (projections, dense_mlp, router, shared, head) == \
        (136_314_880, 37_748_736, 1_048_576, 25_165_824, 51_249_152)
    assert (attention, held) == (92_282_880.0, 12_582_912.0)
    by_hand = 3 * 2 * (projections + attention + dense_mlp + router + shared
                       + held + head)
    assert by_hand == 2_138_357_760
    assert flops_afmoe.train_flops_per_token(c, 8192) == by_hand
    parts = flops_afmoe.forward_flops_per_token(flops_afmoe.shape(c), 8192)
    assert sum(parts.values()) * 3 == by_hand
    # the shares the issue quotes: attention inside the band 26%, projections 38%
    assert round(100 * 2 * attention / sum(parts.values())) == 26
    assert round(100 * 2 * projections / sum(parts.values())) == 38
    # a kernel that masked the window and did not skip it would run the triangle
    masked = 2 * 4096 * 5 * 4096.5
    assert round(masked / 1e6) == 168 and round((masked - attention) / 1e6) == 76


def test_kernel_costs_by_hand_and_the_parts_sum_to_the_step():
    cell = _cell()
    parts = flops_afmoe.parts(cell.config, cell.traffic)
    assert set(parts) == {"flash_fwd", "flash_bwd", "gmm", "xent"}
    pairs = 4 * (2048 * 2049 // 2 + 6144 * 2048) + 8192 * 8193 // 2
    product = 2 * 32 * pairs * 128            # one score-sized product, 32 query heads
    assert parts["flash_fwd"].flops == 2 * product
    assert parts["flash_bwd"].flops == 5 * product
    wide, narrow = 8192 * 32 * 128 * 2, 8192 * 4 * 128 * 2     # bf16, K/V once a KV head
    assert parts["flash_fwd"].hbm_bytes == 5 * (2 * wide + 2 * narrow)
    assert parts["flash_bwd"].hbm_bytes == 5 * (4 * wide + 4 * narrow)
    assert parts["flash_fwd"].least_seconds(V5E) == pytest.approx(0.007670, rel=1e-3)
    assert parts["flash_bwd"].least_seconds(V5E) == pytest.approx(0.019176, rel=1e-3)
    # the experts held: 8,192 x 8 x 8 / 128 = 4,096 rows a layer on average
    gmm = flops_moe.gmm_cost(rows=4096, d_model=2048, d_expert=1024, n_experts=8)
    assert parts["gmm"].flops == 4 * gmm.flops == 4 * 9 * 2 * 4096 * 2048 * 1024
    assert parts["xent"].flops == flops.fused_xent_cost(
        rows=8192, d_model=2048, vocab_size=25024).flops == 4 * 2 * 8192 * 2048 * 25024
    family = cell.load_module("families", "afmoe")
    built = family.build(cell.config, dict(cell.traffic, pool_batches=1), 0, 1,
                         abstract=True)
    total = built.kernel_cost_per_step
    assert total.flops == pytest.approx(sum(p.flops for p in parts.values()))
    assert total.hbm_bytes == pytest.approx(sum(p.hbm_bytes for p in parts.values()))
    assert built.train_flops_per_token == 2_138_357_760
    assert built.tokens_per_step == 8192


def test_the_cut_has_the_parameters_the_configuration_file_counts():
    """Per layer: attention q, gate, o 3 x 2,048 x 4,096, k and v 2 x 2,048 x
    512, two 128-wide QK norms; four norms; the dense MLP or the router, the
    bias, the shared expert and 8 routed experts. (ISSUE 29 wrote 504,148,480:
    it counted the two QK norms as 512 a layer where they are 256.)"""
    import jax
    import numpy as np
    cell = _cell()
    family = cell.load_module("families", "afmoe")
    built = family.build(cell.config, dict(cell.traffic, pool_batches=1), 0, 1,
                         abstract=True)
    attention = 3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128
    expert = 3 * 2048 * 1024
    dense = attention + 4 * 2048 + 3 * 2048 * 6144
    routed = attention + 4 * 2048 + expert + 2048 * 128 + 8 * expert
    assert (attention, dense, routed) == (27_263_232, 65_020_160, 84_156_672)
    weights = dense + 4 * routed + 2 * 25024 * 2048 + 2048
    assert weights == 504_147_200
    leaves = jax.tree_util.tree_leaves_with_path(built.params)
    bias = sum(int(np.prod(x.shape)) for path, x in leaves
               if path[-1].key == "expert_bias")
    assert bias == 4 * 128
    assert sum(int(np.prod(x.shape)) for _, x in leaves) == weights + bias
    assert {str(x.dtype) for _, x in leaves} == {"float32"}
    # 20 bytes a parameter on the chip (PERF.md §4): over the 25% floor before
    # any activation
    assert 20 * weights / 2**30 == pytest.approx(9.39, abs=0.005)
    assert 20 * weights > 0.25 * V5E.hbm_bytes


# ------------------------------------------------------------- the readers

def _record(by_group, busy_s=1.0, steps=4, cell=None):
    device = types.SimpleNamespace(by_group=by_group, busy_s=busy_s)
    trace = types.SimpleNamespace(devices={0: device})
    return {"trace": trace, "trace_steps": steps, "peaks": V5E,
            "cell": cell or _cell()}


def test_new_readers_on_a_trace_made_by_hand():
    record = _record({"pallas:flash_fwd": 0.08, "pallas:flash_bwd_dkv": 0.12,
                      "pallas:flash_bwd_dq": 0.08, "pallas:moe_gmm_fwd": 0.01,
                      "pallas:moe_gmm_bwd_dx": 0.01, "pallas:moe_gmm_bwd_dw": 0.02,
                      "pallas:xent_fwd": 0.1, "fusion (kOutput)": 0.4})
    # 4 steps need 4 x 7.670 ms of forward at the roofline and took 80 ms
    assert window_flash_fwd_roofline_pct.read(record) == pytest.approx(38.35, rel=1e-3)
    assert window_flash_bwd_roofline_pct.read(record) == pytest.approx(38.35, rel=1e-3)
    assert window_flash_time_pct.read(record) == pytest.approx(28.0)
    least = flops_afmoe.parts(record["cell"].config,
                              record["cell"].traffic)["gmm"].least_seconds(V5E)
    assert moe_held_gmm_roofline_pct.read(record) == \
        pytest.approx(100 * 4 * least / 0.04)
    # the split backward's second kernel absent (the one-pass path): the sum holds
    one_pass = _record({"pallas:flash_fwd": 0.08, "pallas:flash_bwd_dkv": 0.2})
    assert window_flash_bwd_roofline_pct.read(one_pass) == pytest.approx(38.35, rel=1e-3)


def test_new_readers_find_nothing_where_there_is_nothing_to_read(monkeypatch):
    # another family's cell, a run without a device trace, a checkout older
    # than the kernels' names: nothing, and no raise
    other = _record({"pallas:flash_fwd": 0.3},
                    cell=harness.load_cell("gpt2m-pretrain-1k", ROOT))
    untraced = {"trace": None, "cell": _cell(), "peaks": V5E, "trace_steps": 4}
    for reader in READERS.values():
        assert reader.read(other) is None
        assert reader.read(untraced) is None
    monkeypatch.setattr(kernel_parts, "program_kernel_names", lambda: None)
    for reader in READERS.values():
        assert reader.read(_record({"pallas:flash_fwd": 0.3})) is None


def test_named_kernels_missing_from_the_trace_fail_the_run():
    for name in ("window_flash_fwd_roofline_pct", "window_flash_bwd_roofline_pct",
                 "moe_held_gmm_roofline_pct"):
        with pytest.raises(harness.BenchmarkError, match="no time under"):
            READERS[name].read(_record({"pallas:jvp__": 0.2}))


# ------------------------------------------------------ BENCHMARK.json

def test_new_entries_name_files_that_exist_and_cut_what_the_issue_names():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by name, not by place: later PRs append theirs
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert work == {"name": CELL, "config": "trinity-mini",
                    "traffic": "pretrain-8k", "chips": 1, "why": work["why"]}
    entry = next(c for c in bench["configs"] if c["name"] == "trinity-mini")
    assert entry["file"] == "benchmark/configs/trinity-mini.json"
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "num_dense_layers", "num_experts", "vocab_size"]
    assert entry["source"] == \
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"
    assert all(1 <= len(x[k]) <= 200 for x in (entry, work)
               for k in ("why", "source") if k in x)
    cell = _cell()
    for sub in ("families", "reference"):
        cell.find(sub, "afmoe.py")
    new = [m for m in bench["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in new] == list(READERS)
    for m in new:
        assert CELL in m["workloads"] and m["layer"] == "kernels"
        assert m["unit"] == "%" and m["moves"] == "tokens_per_s_per_chip"
        assert callable(cell.load_module("layers", m["name"]).read)
    t = cell.traffic
    assert (t["seq_len"], t["micro_batch"], t["accumulation"], t["log_every"],
            t["pool_batches"], t["check_sequences"], t["strategy"], t["mesh"]) \
        == (8192, 1, 1, 8, 8, 1, "AllReduce", {"data": 1})


def test_the_configuration_keeps_every_published_number_but_the_five_cut():
    """Against the catalog's own ``config`` where the guide is installed; the
    cut, the deployment and every assumed fact are stated in the file."""
    config = _cell().config
    cut = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8,
           "vocab_size": 25024,
           "layer_types": ["sliding_attention"] * 4 + ["full_attention"]}
    for key, value in cut.items():
        assert config[key] == value
    assert config["published"] == {
        "num_hidden_layers": 32, "layer_types": "[sliding, sliding, sliding, full] x 8",
        "num_dense_layers": 2, "num_experts": 128, "vocab_size": 200192}
    assert [r.split()[0] for r in config["reduced"]] == list(
        ["num_hidden_layers", "layer_types", "num_dense_layers", "num_experts",
         "vocab_size"])
    widths = dict(hidden_size=2048, num_attention_heads=32, num_key_value_heads=4,
                  head_dim=128, intermediate_size=6144, moe_intermediate_size=1024,
                  num_experts_per_tok=8, num_shared_experts=1, sliding_window=2048,
                  route_scale=2.826, rms_norm_eps=1e-5, rope_theta=10000,
                  route_norm=True, score_func="sigmoid", load_balance_coeff=0.001,
                  mup_enabled=True, max_position_embeddings=131072)
    for key, value in widths.items():
        assert config[key] == value, key
    assert config["router_width"] == 128 and config["first_expert_held"] == 0
    assert "16 chips" in config["deployment"] and "1/16" in config["deployment"]
    assert config["assumed"]["rows_bound"] == 8192
    assert set(config["assumed"]) == {k for keys in config["assumed_why"]
                                      for k in keys.split(", ")}
    assert config["departures"] and config["expects_pallas"] is True
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Trinity-Mini")
    for key, value in row["config"].items():
        if key not in cut:
            assert config[key] == value, key


# ----------------------------------------------------------- CPU rehearsal

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The shared scratch root plus a tiny AFMoE configuration and cell, as
    new files and entries: two layer kinds behind a dense layer, 2 query heads
    a KV head, a window shorter than the sequence, 2 of 8 experts held."""
    root = scratch.make_root(tmp_path_factory.mktemp("trinity_root"))
    with open(os.path.join(ROOT, "benchmark", "configs", "trinity-mini.json")) as f:
        config = json.load(f)
    config.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                  head_dim=16, intermediate_size=96, moe_intermediate_size=32,
                  num_hidden_layers=3, num_dense_layers=1, router_width=8,
                  num_experts=2, first_expert_held=2, num_experts_per_tok=2,
                  layer_types=["sliding_attention", "sliding_attention",
                               "full_attention"],
                  sliding_window=8, vocab_size=503, max_position_embeddings=64)
    config["assumed"] = dict(
        config["assumed"], rows_bound=8, learning_rate=0.003, warmup_steps=4,
        expert_bias_balance={"first_coeff": 0.05, "iterations": 8})
    with open(os.path.join(root, "extra", "configs", "tiny-trinity.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(ROOT, "benchmark", "traffic", "pretrain-8k.json")) as f:
        traffic = json.load(f)
    traffic.update(seq_len=32, micro_batch=2, log_every=2, check_sequences=2)
    with open(os.path.join(root, "extra", "traffic", "tiny-8k.json"), "w") as f:
        json.dump(traffic, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-trinity", "source": "test only",
                             "reduced": [], "why": "test only",
                             "file": "extra/configs/tiny-trinity.json"})
    bench["workloads"].append({"name": "tiny-trinity-8k", "config": "tiny-trinity",
                               "traffic": "tiny-8k", "chips": 1,
                               "why": "test only"})
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_tiny_trinity_cell_end_to_end_on_the_cpu(root, trace):
    line = test_harness_cpu._rehearse(root, "tiny-trinity-8k", devices=1,
                                      trace=trace, seconds=4.0)
    test_harness_cpu._check_shape(line, 1)
    reference = line["checks"]["reference"]
    assert reference["loss_rel_diff"] < 1e-3 and reference["grad_rel_l2"] < 3e-2
    if trace:
        # no device trace on the CPU: the new readers give nothing
        assert not set(line["metrics"]) & set(READERS)
        assert "compiled_hbm_gib" in line["metrics"]
    else:
        assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
