"""What PR 31 added, by new files only: LFM2-24B-A2B's required operations and
its kernels' bytes against counts made by hand, the parameter count of the
cut, the six new readers on a trace made by hand, ``BENCHMARK.json``'s new
entries, and a tiny ``lfm2_moe`` cell end to end on the CPU through
``run_cell(require_tpu=False)``."""

import json
import os
import types

import pytest

from benchmark import flops, flops_lfm2, flops_moe, harness, kernel_parts, peaks
from benchmark.layers import (lfm2_flash_bwd_roofline_pct,
                              lfm2_flash_fwd_roofline_pct,
                              lfm2_held_gmm_roofline_pct,
                              short_conv_bwd_roofline_pct,
                              short_conv_fwd_roofline_pct, short_conv_time_pct)
from benchmark.tests import scratch, test_harness_cpu
from benchmark.tests.conftest import ROOT

V5E = peaks.peaks_for("TPU v5 lite")
CELL = "lfm2-pretrain-8k"
READERS = {"short_conv_fwd_roofline_pct": short_conv_fwd_roofline_pct,
           "short_conv_bwd_roofline_pct": short_conv_bwd_roofline_pct,
           "short_conv_time_pct": short_conv_time_pct,
           "lfm2_flash_fwd_roofline_pct": lfm2_flash_fwd_roofline_pct,
           "lfm2_flash_bwd_roofline_pct": lfm2_flash_bwd_roofline_pct,
           "lfm2_held_gmm_roofline_pct": lfm2_held_gmm_roofline_pct}
CUT = ["num_hidden_layers", "layer_types", "num_dense_layers", "num_experts",
       "vocab_size"]


def _cell():
    return harness.load_cell(CELL, ROOT)


# ------------------------------------------------------------ required work

def test_lfm2_train_flops_per_token_by_hand():
    cell = _cell()
    c = cell.config
    assert (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"],
            c["intermediate_size"], c["moe_intermediate_size"], c["router_width"],
            c["num_experts"], c["num_experts_per_tok"], c["conv_L_cache"],
            c["vocab_size"], c["num_hidden_layers"]) \
        == (2048, 32, 8, 11776, 1536, 64, 8, 4, 3, 8192, 5)
    # operations a token, forward (a multiply-add is two)
    conv = 4 * 2 * 2048 * (6144 + 2048)             # in_proj and out_proj, 4 conv layers
    projections = 2 * 2048 * (2 * 2048 + 2 * 512)   # q, o; k, v
    attention = 2 * 8192 * 2048                     # q.k^T and p.v over half the sequence
    dense_mlp = 3 * 2 * 2048 * 11776
    router = 4 * 2 * 2048 * 64
    held = 4 * 3 * 2 * 2048 * 1536 * (4 * 8 / 64)   # half a held row a token
    head = 2 * 2048 * 8192
    assert (conv, projections, attention, dense_mlp, router, held, head) == \
        (4 * 33_554_432, 20_971_520, 33_554_432, 144_703_488, 1_048_576,
         4 * 9_437_184, 33_554_432)
    forward = conv + projections + attention + dense_mlp + router + held + head
    assert forward == 405_798_912 and 3 * forward == 1_217_396_736
    assert flops_lfm2.train_flops_per_token(c, 8192) == 3 * forward
    parts = flops_lfm2.forward_flops_per_token(flops_lfm2.shape(c), 8192)
    assert sum(parts.values()) == forward
    # the shares the issue quotes
    share = {k: round(100 * v / forward, 1) for k, v in parts.items()}
    assert (share["dense_mlp"], share["conv_operators"], share["held_experts"],
            share["head"]) == (35.7, 33.1, 9.3, 8.3)
    assert round(100 * (projections + attention) / forward, 1) == 13.4


def test_kernel_costs_by_hand_and_the_parts_sum_to_the_step():
    cell = _cell()
    parts = flops_lfm2.parts(cell.config, cell.traffic)
    assert set(parts) == {"conv_fwd", "conv_bwd", "flash_fwd", "flash_bwd",
                          "gmm", "xent"}
    # the convolution: no matrix product; 8 and 14 bytes an element of
    # [16,384, 2,048], four layers: 0.33 ms and 0.57 ms a layer at 819 GB/s
    elements = 16_384 * 2048
    assert parts["conv_fwd"] == flops.KernelCost(0.0, 4 * 8 * elements)
    assert parts["conv_bwd"] == flops.KernelCost(0.0, 4 * 14 * elements)
    assert parts["conv_fwd"].least_seconds(V5E) / 4 == pytest.approx(0.3278e-3, rel=1e-3)
    assert parts["conv_bwd"].least_seconds(V5E) / 4 == pytest.approx(0.5736e-3, rel=1e-3)
    assert parts["conv_fwd"].bound(V5E) == "memory"
    # flash: 2 sequences, 32 query heads of 64 over the causal triangle
    product = 2 * 2 * 32 * (8192 * 8193 // 2) * 64
    assert parts["flash_fwd"].flops == 2 * product
    assert parts["flash_bwd"].flops == 5 * product
    wide, narrow = 2 * 8192 * 32 * 64 * 2, 2 * 8192 * 8 * 64 * 2    # bf16, K/V once a KV head
    assert parts["flash_fwd"].hbm_bytes == 2 * wide + 2 * narrow
    assert parts["flash_bwd"].hbm_bytes == 4 * wide + 4 * narrow
    assert parts["flash_fwd"].bound(V5E) == "compute"
    # the experts held: 16,384 x 4 x 8 / 64 = 8,192 rows a layer on average
    gmm = flops_moe.gmm_cost(rows=8192, d_model=2048, d_expert=1536, n_experts=8)
    assert parts["gmm"].flops == 4 * gmm.flops == 4 * 9 * 2 * 8192 * 2048 * 1536
    assert parts["xent"].flops == flops.fused_xent_cost(
        rows=16_384, d_model=2048, vocab_size=8192).flops
    family = cell.load_module("families", "lfm2_moe")
    built = family.build(cell.config, dict(cell.traffic, pool_batches=1), 0, 2,
                         abstract=True)
    total = built.kernel_cost_per_step
    assert total.flops == pytest.approx(sum(p.flops for p in parts.values()))
    assert total.hbm_bytes == pytest.approx(sum(p.hbm_bytes for p in parts.values()))
    assert built.train_flops_per_token == 1_217_396_736
    assert built.tokens_per_step == 16_384
    # a step: 19.9 TFLOP required
    assert round(built.train_flops_per_token * built.tokens_per_step / 1e12, 1) == 19.9


def test_the_cut_has_the_parameters_the_configuration_file_counts():
    """Per layer: the conv operator 2,048 x 6,144 + 2,048 x 3 + 2,048 x 2,048,
    or attention q, o 2 x 2,048 x 2,048, k, v 2 x 2,048 x 512, two 64-wide QK
    norms; two norms; the dense MLP, or the router, the bias and 8 routed
    experts; the tied table once; the final norm."""
    import jax
    import numpy as np
    cell = _cell()
    family = cell.load_module("families", "lfm2_moe")
    built = family.build(cell.config, dict(cell.traffic, pool_batches=1), 0, 2,
                         abstract=True)
    conv = 2048 * 6144 + 2048 * 3 + 2048 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    expert = 3 * 2048 * 1536
    dense_conv = conv + 2 * 2048 + 3 * 2048 * 11776
    routed_conv = conv + 2 * 2048 + 2048 * 64 + 8 * expert
    routed_attention = attention + 2 * 2048 + 2048 * 64 + 8 * expert
    assert (conv, attention, expert) == (16_783_360, 10_485_888, 9_437_184)
    assert (dense_conv, routed_conv, routed_attention) == \
        (89_139_200, 92_416_000, 86_118_528)
    weights = dense_conv + 3 * routed_conv + routed_attention + 8192 * 2048 + 2048
    assert weights == 469_284_992
    leaves = jax.tree_util.tree_leaves_with_path(built.params)
    bias = sum(int(np.prod(x.shape)) for path, x in leaves
               if path[-1].key == "expert_bias")
    assert bias == 4 * 64 == 256
    assert sum(int(np.prod(x.shape)) for _, x in leaves) == weights + bias
    assert {str(x.dtype) for _, x in leaves} == {"float32"}
    assert "469,284,992" in cell.config["reduced_why"]
    # 20 bytes a parameter on the chip (PERF.md §4): over the 25% floor before
    # any activation
    assert 20 * weights / 2**30 == pytest.approx(8.74, abs=0.005)
    assert 20 * weights > 0.25 * V5E.hbm_bytes


# ------------------------------------------------------------- the readers

def _record(by_group, busy_s=1.0, steps=4, cell=None):
    device = types.SimpleNamespace(by_group=by_group, busy_s=busy_s)
    trace = types.SimpleNamespace(devices={0: device})
    return {"trace": trace, "trace_steps": steps, "peaks": V5E,
            "cell": cell or _cell()}


def test_new_readers_on_a_trace_made_by_hand():
    record = _record({"pallas:short_conv_fwd": 0.008, "pallas:short_conv_bwd": 0.012,
                      "pallas:flash_fwd": 0.02, "pallas:flash_bwd_dkv": 0.05,
                      "pallas:moe_gmm_fwd": 0.02, "pallas:moe_gmm_bwd_dx": 0.02,
                      "pallas:moe_gmm_bwd_dw": 0.04, "pallas:xent_fwd": 0.1,
                      "fusion (kOutput)": 0.4})
    # 4 steps of 4 layers need 16 x 0.3278 ms of forward at the memory
    # bandwidth and took 8 ms; 16 x 0.5736 ms of backward and took 12 ms
    assert short_conv_fwd_roofline_pct.read(record) == pytest.approx(65.55, rel=1e-3)
    assert short_conv_bwd_roofline_pct.read(record) == pytest.approx(76.48, rel=1e-3)
    assert short_conv_time_pct.read(record) == pytest.approx(2.0)
    parts = flops_lfm2.parts(record["cell"].config, record["cell"].traffic)
    least = {k: v.least_seconds(V5E) for k, v in parts.items()}
    assert least["flash_fwd"] == pytest.approx(2.791e-3, rel=1e-3)
    assert lfm2_flash_fwd_roofline_pct.read(record) == \
        pytest.approx(100 * 4 * least["flash_fwd"] / 0.02)
    # the one-pass backward holds no flash_bwd_dq: the sum holds
    assert lfm2_flash_bwd_roofline_pct.read(record) == \
        pytest.approx(100 * 4 * least["flash_bwd"] / 0.05)
    assert lfm2_held_gmm_roofline_pct.read(record) == \
        pytest.approx(100 * 4 * least["gmm"] / 0.08)
    for reader in READERS.values():
        assert 0 < reader.read(record) <= 100


def test_new_readers_find_nothing_where_there_is_nothing_to_read(monkeypatch):
    # another family's cell, a run without a device trace, a checkout older
    # than the kernels' names, one older than the convolution: nothing, and
    # no raise
    groups = {"pallas:flash_fwd": 0.3, "pallas:short_conv_fwd": 0.1}
    untraced = {"trace": None, "cell": _cell(), "peaks": V5E, "trace_steps": 4}
    for other in ("gpt2m-pretrain-1k", "trinity-pretrain-8k", "olmoe-pretrain-4k"):
        record = _record(groups, cell=harness.load_cell(other, ROOT))
        for reader in READERS.values():
            assert reader.read(record) is None
    for reader in READERS.values():
        assert reader.read(untraced) is None
    older = tuple(n for n in kernel_parts.program_kernel_names()
                  if not n.startswith("short_conv"))
    for names in (None, older):
        monkeypatch.setattr(kernel_parts, "program_kernel_names", lambda: names)
        for reader in READERS.values():
            assert reader.read(_record(groups)) is None


def test_named_kernels_missing_from_the_trace_fail_the_run():
    for name in READERS:
        if name.endswith("roofline_pct"):
            with pytest.raises(harness.BenchmarkError, match="no time under"):
                READERS[name].read(_record({"pallas:jvp__": 0.2}))


# ------------------------------------------------------ BENCHMARK.json

def test_new_entries_name_files_that_exist_and_cut_what_the_issue_names():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by name, not by place: later PRs append theirs
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert work == {"name": CELL, "config": "lfm2-24b-a2b",
                    "traffic": "pretrain-8k-b2", "chips": 1, "why": work["why"]}
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-24b-a2b")
    assert entry["file"] == "benchmark/configs/lfm2-24b-a2b.json"
    assert entry["reduced"] == CUT
    assert entry["source"].startswith(
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    assert "lfm2_moe" in entry["source"]
    assert all(1 <= len(x[k]) <= 200 for x in (entry, work)
               for k in ("why", "source") if k in x)
    cell = _cell()
    for sub in ("families", "reference"):
        cell.find(sub, "lfm2_moe.py")
    new = [m for m in bench["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in new] == list(READERS)
    for m in new:
        assert m["workloads"] == [CELL] and m["layer"] == "kernels"
        assert m["unit"] == "%" and m["moves"] == "tokens_per_s_per_chip"
        assert m["source"] == "device_trace"
        assert m["better"] == ("lower" if m["name"].endswith("time_pct") else "higher")
        assert callable(cell.load_module("layers", m["name"]).read)
    # one cell in four may take four chips; this one takes one
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)
    t = cell.traffic
    assert (t["seq_len"], t["micro_batch"], t["accumulation"], t["log_every"],
            t["pool_batches"], t["check_sequences"], t["strategy"], t["mesh"]) \
        == (8192, 2, 1, 8, 8, 1, "AllReduce", {"data": 1})
    # the sibling cell's traffic but for the second sequence
    with open(os.path.join(ROOT, "benchmark", "traffic", "pretrain-8k.json")) as f:
        sibling = json.load(f)
    assert {k for k in t if t[k] != sibling[k]} == {"micro_batch", "note"}


def test_the_configuration_keeps_every_published_number_but_the_five_cut():
    """Against the catalog's own ``config`` where the guide is installed; the
    cut, the deployment and every assumed fact are stated in the file."""
    config = _cell().config
    cut = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8,
           "vocab_size": 8192,
           "layer_types": ["conv", "conv", "conv", "full_attention", "conv"]}
    for key, value in cut.items():
        assert config[key] == value
    assert config["published"] == {
        "num_hidden_layers": 40, "layer_types": "[conv, conv, full_attention, conv] x 10",
        "num_dense_layers": 2, "num_experts": 64, "vocab_size": 65536}
    assert [r.split()[0] for r in config["reduced"]] == CUT
    widths = dict(hidden_size=2048, num_attention_heads=32, num_key_value_heads=8,
                  intermediate_size=11776, moe_intermediate_size=1536,
                  num_experts_per_tok=4, conv_L_cache=3, conv_bias=False,
                  norm_eps=1e-5, norm_topk_prob=True, routed_scaling_factor=1,
                  use_expert_bias=True, max_position_embeddings=128000,
                  rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
                  model_type="lfm2_moe")
    for key, value in widths.items():
        assert config[key] == value, key
    assert config["family"] == "lfm2_moe"
    assert config["router_width"] == 64 and config["first_expert_held"] == 0
    assert "8 chips" in config["deployment"] and "an eighth" in config["deployment"]
    assumed = config["assumed"]
    assert (assumed["rows_bound"], assumed["route_eps"], assumed["conv_impl"],
            assumed["attention_impl"], assumed["fused_head"],
            assumed["load_balance_coeff"]) == (16384, 1e-6, "pallas", "flash",
                                               True, 0.001)
    assert set(assumed) == {k for keys in config["assumed_why"]
                            for k in keys.split(", ")}
    assert config["departures"] and config["expects_pallas"] is True
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-24B-A2B")
    assert row["config"]["layer_types"][4:8] == cut["layer_types"][1:]   # one period
    assert row["config"]["layer_types"][:2] == ["conv", "conv"]         # the dense ones
    for key, value in row["config"].items():
        if key not in cut:
            assert config[key] == value, key


# ----------------------------------------------------------- CPU rehearsal

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The shared scratch root plus a tiny LFM2-MoE configuration and cell, as
    new files and entries: both layer kinds behind a dense conv layer, 2 query
    heads a KV head, 2 of 8 experts held, two sequences a step."""
    root = scratch.make_root(tmp_path_factory.mktemp("lfm2_root"))
    with open(os.path.join(ROOT, "benchmark", "configs", "lfm2-24b-a2b.json")) as f:
        config = json.load(f)
    config.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
                  intermediate_size=96, moe_intermediate_size=32,
                  num_hidden_layers=3, num_dense_layers=1, router_width=8,
                  num_experts=2, first_expert_held=2, num_experts_per_tok=2,
                  layer_types=["conv", "full_attention", "conv"],
                  vocab_size=503, max_position_embeddings=64)
    config["assumed"] = dict(
        config["assumed"], rows_bound=8, learning_rate=0.003, warmup_steps=4,
        expert_bias_balance={"first_coeff": 0.05, "iterations": 8})
    with open(os.path.join(root, "extra", "configs", "tiny-lfm2.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(ROOT, "benchmark", "traffic", "pretrain-8k-b2.json")) as f:
        traffic = json.load(f)
    traffic.update(seq_len=32, log_every=2, check_sequences=2)
    with open(os.path.join(root, "extra", "traffic", "tiny-8k-b2.json"), "w") as f:
        json.dump(traffic, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-lfm2", "source": "test only",
                             "reduced": [], "why": "test only",
                             "file": "extra/configs/tiny-lfm2.json"})
    bench["workloads"].append({"name": "tiny-lfm2-8k", "config": "tiny-lfm2",
                               "traffic": "tiny-8k-b2", "chips": 1,
                               "why": "test only"})
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_tiny_lfm2_cell_end_to_end_on_the_cpu(root, trace):
    line = test_harness_cpu._rehearse(root, "tiny-lfm2-8k", devices=1,
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
