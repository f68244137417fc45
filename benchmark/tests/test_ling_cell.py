"""What PR 52 added, by new files only: Ling-3.0-flash's required operations
and its kernels' operations and bytes against counts made by hand, the
parameter count of the cut, the eight new readers on a trace made by hand,
``BENCHMARK.json``'s new entries against the catalog's numbers, the cell's
step compiled for a described v5e, and a tiny ``bailing_hybrid`` cell end to
end on the CPU through ``run_cell(require_tpu=False)``."""

import json
import os
import types

import pytest

from benchmark import (flops_bailing_hybrid, harness, kernel_parts, peaks,
                       rehearse)
from benchmark.layers import (kda_bwd_roofline_pct, kda_fwd_roofline_pct,
                              kda_time_pct, ling_conv_silu_bwd_roofline_pct,
                              ling_conv_silu_fwd_roofline_pct,
                              ling_held_gmm_roofline_pct,
                              ling_mla_flash_bwd_roofline_pct,
                              ling_mla_flash_fwd_roofline_pct)
from benchmark.tests import scratch, test_harness_cpu
from benchmark.tests.conftest import ROOT

V5E = peaks.peaks_for("TPU v5 lite")
CELL = "ling-pretrain-8k"
CONFIG = "ling-3.0-flash"
READERS = {"kda_fwd_roofline_pct": kda_fwd_roofline_pct,
           "kda_bwd_roofline_pct": kda_bwd_roofline_pct,
           "kda_time_pct": kda_time_pct,
           "ling_conv_silu_fwd_roofline_pct": ling_conv_silu_fwd_roofline_pct,
           "ling_conv_silu_bwd_roofline_pct": ling_conv_silu_bwd_roofline_pct,
           "ling_mla_flash_fwd_roofline_pct": ling_mla_flash_fwd_roofline_pct,
           "ling_mla_flash_bwd_roofline_pct": ling_mla_flash_bwd_roofline_pct,
           "ling_held_gmm_roofline_pct": ling_held_gmm_roofline_pct}
CUT = {"num_hidden_layers": (42, 7), "first_k_dense_replace": (2, 1),
       "num_attention_heads": (32, 16), "num_key_value_heads": (32, 16),
       "num_experts": (512, 8), "vocab_size": (157184, 19648)}
CHIP_GIB = 15.75      # what a v5e's runtime leaves of its 16 GiB


def _cell():
    return harness.load_cell(CELL, ROOT)


# ------------------------------------------------------------ required work

def test_ling_train_flops_per_token_by_hand():
    c = _cell().config
    s = flops_bailing_hybrid.shape(c)
    assert (s["n_kda"], s["n_mla"], s["n_dense"], s["n_expert"], s["chunk"]) \
        == (6, 1, 1, 6, 64)
    d, wide = 2560, 16 * 128
    # a KDA layer: q, k, v, the decay, the gate and o, and beta's 16 columns
    kda_projections = 2 * (6 * d * wide + d * 16)
    # the recurrence a token and head: three triangles over half a chunk's
    # pairs, the solve as a substitution, three products with the state
    a_head = 3 * 64 * 128 // 2 + 64 * 128 + 3 * 128 * 128
    assert a_head == 69_632
    recurrence = 2 * 16 * a_head
    mla_projections = 2 * (d * 16 * 192 + d * 576 + 512 * 16 * 256
                           + 16 * 128 * d + d * 16)
    attention = 8192 * 16 * (192 + 128)        # half the sequence, two products
    one_expert = 3 * 2 * d * 768
    by_part = flops_bailing_hybrid.forward_flops_per_token(s, 8192)
    assert by_part == {
        "kda_projections": 6 * kda_projections, "kda_recurrence": 6 * recurrence,
        "mla_projections": mla_projections, "attention": attention,
        "dense_mlp": 3 * 2 * d * 6144, "router": 6 * 2 * d * 512,
        "shared_experts": 6 * one_expert, "held_experts": 6 * one_expert / 8,
        "head": 2 * d * 19648}
    forward = sum(by_part.values())
    assert forward == 757_055_488
    assert flops_bailing_hybrid.train_flops_per_token(c, 8192) == 3 * forward
    # the step: 8,192 tokens x 2.27 GFLOP = 18.6 TFLOP required; the six KDA
    # mixers 52% of it, the recurrence itself 1.8%
    assert 3 * forward * 8192 == pytest.approx(18.6e12, rel=2e-3)
    mixers = by_part["kda_projections"] + by_part["kda_recurrence"]
    assert round(100 * mixers / forward) == 52
    assert round(100 * by_part["kda_recurrence"] / forward, 1) == 1.8


def test_kernel_costs_by_hand_and_the_parts_sum_to_the_step():
    cell = _cell()
    parts = flops_bailing_hybrid.parts(cell.config, cell.traffic)
    assert set(parts) == {"kda_fwd", "kda_bwd", "conv_fwd", "conv_bwd",
                          "flash_fwd", "flash_bwd", "gmm", "xent"}
    fwd, bwd = flops_bailing_hybrid.kda_cost(batch=1, seq_len=8192, heads=16,
                                             head_dim=128, chunk=64)
    assert fwd.flops == 8192 * 2 * 16 * 69_632 and bwd.flops == 2 * fwd.flops
    rows = 8192 * 16 * 128
    states = 128 * 16 * 128 * 128 * 4          # one float32 state a chunk and head
    assert states == 128 << 20
    # q, k, v, o at two bytes and the float32 log-decay; the states written
    assert fwd.hbm_bytes == rows * (4 * 2 + 4) + states
    # q, k, v, dO, dq, dk, dv at two bytes, g and dg float32; the states read
    assert bwd.hbm_bytes == rows * (7 * 2 + 2 * 4) + states
    assert fwd.bound(V5E) == bwd.bound(V5E) == "memory"
    assert fwd.least_seconds(V5E) == pytest.approx(0.4097e-3, rel=1e-3)
    assert bwd.least_seconds(V5E) == pytest.approx(0.6146e-3, rel=1e-3)
    # a ragged length is counted in whole chunks
    assert flops_bailing_hybrid.kda_cost(
        batch=1, seq_len=8191, heads=16, head_dim=128, chunk=64)[0] == fwd
    assert parts["kda_fwd"].flops == 6 * fwd.flops
    assert parts["kda_bwd"].hbm_bytes == 6 * bwd.hbm_bytes
    # three convolutions a KDA layer over [8,192, 2,048] at two bytes
    array = 8192 * 2048 * 2
    assert parts["conv_fwd"] == flops_bailing_hybrid.flops.KernelCost(
        0.0, 18 * 2 * array)
    assert parts["conv_bwd"].hbm_bytes == 18 * 3 * array
    # the held experts receive 1,024 of the 65,536 choices on average
    assert flops_bailing_hybrid.flops_afmoe.held_rows(
        8192, flops_bailing_hybrid.shape(cell.config)) == 1024
    whole = flops_bailing_hybrid.kernel_cost_per_step(cell.config, cell.traffic)
    assert whole.flops == pytest.approx(sum(p.flops for p in parts.values()))
    assert whole.hbm_bytes == pytest.approx(
        sum(p.hbm_bytes for p in parts.values()))
    by_part = flops_bailing_hybrid.forward_flops_per_token(
        flops_bailing_hybrid.shape(cell.config), 8192)
    assert parts["kda_fwd"].flops == pytest.approx(
        by_part["kda_recurrence"] * 8192)
    assert parts["flash_fwd"].flops == pytest.approx(
        by_part["attention"] * 8192, rel=2e-4)    # the triangle's diagonal


def test_the_cut_has_the_parameters_the_configuration_file_counts():
    import jax
    import numpy as np
    cell = _cell()
    family = cell.load_module("families", "bailing_hybrid")
    built = family.build(cell.config, dict(cell.traffic, pool_batches=1), 0, 1,
                         abstract=True)
    h = 16
    kda = 6 * 2560 * 128 * h + 2560 * h + 3 * 4 * 128 * h + h + 128 * h + 128
    latent = (2560 * 192 * h + 2560 * 576 + 512 + 512 * 256 * h
              + 128 * h * 2560 + 2560 * h)
    assert (kda, latent) == (31_525_008, 16_720_384)
    ffn = 9 * 3 * 2560 * 768 + 2560 * 512 + 512     # 8 held + shared, the router
    dense = 3 * 2560 * 6144
    total = (kda + dense + 5 * (kda + ffn) + latent + ffn + 7 * 2 * 2560
             + 2 * 19648 * 2560 + 2560)
    assert total == 680_064_864 == cell.config["parameters"]
    leaves = jax.tree_util.tree_leaves_with_path(built.params)
    assert sum(int(np.prod(x.shape)) for _, x in leaves) == total
    assert {str(x.dtype) for _, x in leaves} == {"float32"}
    assert "680,064,864" in cell.config["reduced_why"]
    # 20 bytes a parameter on the chip (PERF.md section 4): 12.67 GiB of 15.75
    assert 20 * total / 2**30 == pytest.approx(12.67, abs=0.005)
    assert 0.25 * V5E.hbm_bytes < 20 * total < V5E.hbm_bytes
    assert built.pool[0]["tokens"].shape == (1, 8193)
    assert 0 <= built.pool[0]["tokens"].min() and \
        built.pool[0]["tokens"].max() < 19648
    assert built.reference_config["layer_types"] == ("kda",) * 5 + ("mla", "kda")
    assert built.reference_config["n_heads"] == 16


def test_a_configuration_that_is_another_model_is_refused():
    cell = _cell()
    family = cell.load_module("families", "bailing_hybrid")
    for change, message in (
            ({"n_group": 3}, "n_group equal groups"),
            ({"linear_silu": False}, "computes linear_silu"),
            ({"layer_types": ["kda"] * 7}, "the model's own first layers"),
            ({"num_hidden_layers": 36, "layer_types": (["kda"] * 5 + ["mla"]) * 6},
             "clamp their experts")):
        with pytest.raises(ValueError, match=message):
            family.model_config({**cell.config, **change})


# ------------------------------------------------------------- the readers

def _record(by_group, busy_s=1.0, steps=2, cell=None):
    device = types.SimpleNamespace(by_group=by_group, busy_s=busy_s)
    trace = types.SimpleNamespace(devices={0: device})
    return {"trace": trace, "trace_steps": steps, "peaks": V5E,
            "cell": cell or _cell()}


GROUPS = {"pallas:kda_fwd": 0.040, "pallas:kda_bwd": 0.100,
          "pallas:conv_silu_fwd": 0.012, "pallas:conv_silu_bwd": 0.010,
          "pallas:flash_fwd": 0.005, "pallas:flash_bwd_dkv": 0.012,
          "pallas:moe_gmm_fwd": 0.004, "pallas:moe_gmm_bwd_dx": 0.004,
          "pallas:moe_gmm_bwd_dw": 0.008, "fusion (kOutput)": 0.4}


def test_new_readers_on_a_trace_made_by_hand():
    record = _record(GROUPS, busy_s=2.0)
    # 2 steps need 2 x 2.458 ms of the forward and took 40; 2 x 3.687 of 100
    assert kda_fwd_roofline_pct.read(record) == pytest.approx(12.29, rel=1e-3)
    assert kda_bwd_roofline_pct.read(record) == pytest.approx(7.375, rel=1e-3)
    assert kda_time_pct.read(record) == pytest.approx(7.0)
    assert ling_conv_silu_fwd_roofline_pct.read(record) == pytest.approx(
        100 * 2 * 1.47492e-3 / 0.012, rel=1e-3)
    assert ling_conv_silu_bwd_roofline_pct.read(record) == pytest.approx(
        100 * 2 * 2.21238e-3 / 0.010, rel=1e-3)
    assert ling_mla_flash_fwd_roofline_pct.read(record) == pytest.approx(
        100 * 2 * 1.74436e-3 / 0.005, rel=1e-3)
    assert ling_mla_flash_bwd_roofline_pct.read(record) == pytest.approx(
        100 * 2 * 4.53534e-3 / 0.012, rel=1e-3)
    assert ling_held_gmm_roofline_pct.read(record) == pytest.approx(
        100 * 2 * 3.21486e-3 / 0.016, rel=1e-3)
    for reader in READERS.values():
        assert 0 < reader.read(record) <= 100


def test_new_readers_find_nothing_where_there_is_nothing_to_read(monkeypatch):
    # another family's cell, a run without a device trace, a program older
    # than the recurrence's names: nothing, and no raise
    untraced = {"trace": None, "cell": _cell(), "peaks": V5E, "trace_steps": 4}
    for other in ("gpt2m-pretrain-1k", "kanana-pretrain-16k",
                  "nemotron-pretrain-8k"):
        record = _record(GROUPS, cell=harness.load_cell(other, ROOT))
        for reader in READERS.values():
            assert reader.read(record) is None
    for reader in READERS.values():
        assert reader.read(untraced) is None
    names = tuple(n for n in kernel_parts.program_kernel_names()
                  if not n.startswith("kda_"))
    for parent_names in (None, names):
        monkeypatch.setattr(kernel_parts, "program_kernel_names",
                            lambda names=parent_names: names)
        for reader in READERS.values():
            assert reader.read(_record(GROUPS)) is None


def test_named_kernels_missing_from_the_trace_fail_the_run():
    for name, reader in READERS.items():
        if name != "kda_time_pct":
            with pytest.raises(harness.BenchmarkError, match="no time under"):
                reader.read(_record({"pallas:jvp__": 0.2}))


# ------------------------------------------------------ BENCHMARK.json

def test_new_entries_name_files_that_exist_and_cut_what_the_issue_names():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by name, not by place: later PRs append theirs
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert work == {"name": CELL, "config": CONFIG,
                    "traffic": "pretrain-8k-ep64", "chips": 1,
                    "why": work["why"]}
    assert "52%" in work["why"] and "1/64" in work["why"]
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == list(CUT)
    assert entry["source"] == ("https://huggingface.co/inclusionAI/"
                               "Ling-3.0-flash/blob/main/config.json")
    assert all(1 <= len(x[k]) <= 200 for x in (entry, work)
               for k in ("why", "source") if k in x)
    cell = _cell()
    for sub in ("families", "reference"):
        cell.find(sub, "bailing_hybrid.py")
    new = [m for m in bench["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in new] == list(READERS)
    for m in new:
        assert m["workloads"] == [CELL] and m["layer"] == "kernels"
        assert m["unit"] == "%" and m["moves"] == "tokens_per_s_per_chip"
        assert m["source"] == "device_trace"
        assert m["better"] == ("lower" if m["name"].endswith("time_pct") else "higher")
        assert callable(cell.load_module("layers", m["name"]).read)
    kept = next(m for m in bench["per_layer"] if m["name"] == "hbm_kept_gib")
    assert kept["workloads"][-1] == CELL
    # one cell in four may take four chips: 3 of 14, and this one takes one
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    if len(bench["workloads"]) == 14:
        assert four == 3
    assert [w["name"] for w in bench["workloads"]
            if w["traffic"] == "pretrain-8k-ep64"] == [CELL]
    t = cell.traffic
    assert (t["seq_len"], t["micro_batch"], t["accumulation"], t["pool_batches"],
            t["check_sequences"], t["strategy"], t["mesh"], t["job"]) \
        == (8192, 1, 1, 8, 1, "AllReduce", {"data": 1}, "train")
    # every metric without a list of cells applies to the new cell too
    assert {m["name"] for m in cell.per_layer} >= {
        m["name"] for m in bench["per_layer"] if "workloads" not in m}


def test_the_configuration_keeps_every_published_number_but_the_cut():
    """Against the catalog's own ``config`` where the guide is installed; the
    cut, the deployment and every assumed fact are stated in the file."""
    config = _cell().config
    for key, (published, held) in CUT.items():
        assert config[key] == held and config["published"][key] == published
    assert set(config["published"]) == set(CUT)
    assert [r.split()[0] for r in config["reduced"]] == list(CUT)
    assert config["layer_types"] == ["kda"] * 5 + ["mla", "kda"]
    widths = dict(hidden_size=2560, head_dim=128, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, qk_head_dim=192, v_head_dim=128,
                  kv_lora_rank=512, moe_intermediate_size=768,
                  moe_shared_expert_intermediate_size=768,
                  intermediate_size=6144, router_width=512,
                  num_experts_per_tok=8, n_group=8, topk_group=4,
                  short_conv_kernel_size=4, layer_group_size=6,
                  kda_lower_bound=-5, routed_scaling_factor=2.5,
                  rope_theta=6000000, rms_norm_eps=1e-6,
                  model_type="bailing_hybrid")
    for key, value in widths.items():
        assert config[key] == value, key
    assert config["family"] == "bailing_hybrid"
    assert (config["layer_heads"], config["first_head_held"],
            config["first_expert_held"]) == (32, 0, 0)
    assert "64 chips" in config["deployment"] and "1/64" in config["deployment"]
    assumed = config["assumed"]
    assert (assumed["attention_impl"], assumed["kda_impl"], assumed["kda_chunk"],
            assumed["fused_head"], assumed["remat"], assumed["rows_bound"],
            assumed["optimizer"]) == ("flash", "pallas", 64, True, True, 2048,
                                      "adamw")
    assert set(assumed) == {k for keys in config["assumed_why"]
                            for k in keys.split(", ")}
    assert config["departures"] and config["expects_pallas"] is True
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ling-3.0-flash")
    assert row["source_url"] in config["source"]
    for key, value in row["config"].items():
        if key not in CUT:
            assert config[key] == value, key
        else:
            assert config["published"][key] == value, key


# ----------------------------------------------- the chip's compiler, no chip

@pytest.fixture(scope="module")
def topology():
    try:
        return rehearse.describe_topology()
    except Exception as e:  # noqa: BLE001 — any failure to describe is a skip
        pytest.skip(f"cannot describe a {rehearse.TOPOLOGY} topology here: {e}")


def test_the_cell_compiles_for_the_described_chip_under_its_limit(topology):
    cell = _cell()
    with rehearse.steer_kernels_to_compile():
        facts = rehearse.compile_cell(cell, topology.devices)
    print(json.dumps(facts))
    params_gib = facts["parameters"] * 4 / 2**30     # the copy train() holds
    assert facts["parameters"] == 680_064_864
    assert facts["step_gib"] + params_gib < CHIP_GIB - 0.3
    assert facts["step_gib"] > 0.25 * 16             # the contract's floor
    assert facts["tpu_custom_call"] and facts["collectives"] == []


# ----------------------------------------------------------- CPU rehearsal

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The shared scratch root plus a tiny Ling configuration and cell, as new
    files and entries: three layers (kda, mla, kda; the first dense), 2 of 4
    heads of 16 held, 4 of 16 experts in 4 groups of which 2 stay."""
    root = scratch.make_root(tmp_path_factory.mktemp("ling_root"))
    with open(os.path.join(ROOT, "benchmark", "configs", f"{CONFIG}.json")) as f:
        config = json.load(f)
    config.update(hidden_size=64, num_attention_heads=2, num_key_value_heads=2,
                  layer_heads=4, first_head_held=2, head_dim=16,
                  qk_nope_head_dim=8, qk_rope_head_dim=4, qk_head_dim=12,
                  rotary_dim=4, v_head_dim=8, kv_lora_rank=12,
                  intermediate_size=96, moe_intermediate_size=16,
                  moe_shared_expert_intermediate_size=16, router_width=16,
                  num_experts=4, first_expert_held=4, num_experts_per_tok=4,
                  n_group=4, topk_group=2, num_hidden_layers=3,
                  layer_group_size=2, layer_types=["kda", "mla", "kda"],
                  vocab_size=48, max_position_embeddings=256)
    config["assumed"] = dict(
        config["assumed"], learning_rate=0.003, warmup_steps=4, rows_bound=64,
        attention_impl="dot", kda_impl="xla", fused_head=False,
        expert_bias_balance={"first_coeff": 0.05, "iterations": 8})
    with open(os.path.join(root, "extra", "configs", "tiny-ling.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "pretrain-8k-ep64.json")) as f:
        traffic = json.load(f)
    traffic.update(seq_len=96, micro_batch=2, check_sequences=2, log_every=2)
    with open(os.path.join(root, "extra", "traffic", "tiny-ep64.json"), "w") as f:
        json.dump(traffic, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-ling", "source": "test only",
                             "reduced": [], "why": "test only",
                             "file": "extra/configs/tiny-ling.json"})
    bench["workloads"].append({"name": "tiny-ling-ep64", "config": "tiny-ling",
                               "traffic": "tiny-ep64", "chips": 1,
                               "why": "test only"})
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_tiny_ling_cell_end_to_end_on_the_cpu(root, trace):
    line = test_harness_cpu._rehearse(root, "tiny-ling-ep64", devices=1,
                                      trace=trace, seconds=4.0)
    test_harness_cpu._check_shape(line, 1)
    reference = line["checks"]["reference"]
    assert reference["loss_rel_diff"] < 1e-3 and reference["grad_rel_l2"] < 3e-2
    if trace:
        # no device trace on the CPU: the trace's readers give nothing
        assert not set(line["metrics"]) & set(READERS)
        assert "compiled_hbm_gib" in line["metrics"]
    else:
        assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
