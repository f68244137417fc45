"""What PR 35 added, by new files only: Nemotron-3-Nano-30B-A3B's required
operations and its kernels' operations and bytes against counts made by hand,
the parameter count of the cut, the six new readers on a trace made by hand,
``BENCHMARK.json``'s new entries, and a tiny ``nemotron_h`` cell end to end on
the CPU through ``run_cell(require_tpu=False)``."""

import json
import os
import types

import pytest

from benchmark import (flops, flops_afmoe, flops_nemotron_h, harness,
                       kernel_parts, peaks)
from benchmark.layers import (conv_silu_bwd_roofline_pct,
                              conv_silu_fwd_roofline_pct,
                              nemotron_flash_bwd_roofline_pct,
                              nemotron_flash_fwd_roofline_pct,
                              nemotron_held_gmm_roofline_pct,
                              ssd_bwd_roofline_pct, ssd_fwd_roofline_pct,
                              ssd_time_pct)
from benchmark.tests import scratch, test_harness_cpu
from benchmark.tests.conftest import ROOT

V5E = peaks.peaks_for("TPU v5 lite")
CELL = "nemotron-pretrain-8k"
CONFIG = "nemotron-3-nano-30b-a3b"
READERS = {"ssd_fwd_roofline_pct": ssd_fwd_roofline_pct,
           "ssd_bwd_roofline_pct": ssd_bwd_roofline_pct,
           "ssd_time_pct": ssd_time_pct,
           "nemotron_flash_fwd_roofline_pct": nemotron_flash_fwd_roofline_pct,
           "nemotron_flash_bwd_roofline_pct": nemotron_flash_bwd_roofline_pct,
           "nemotron_held_gmm_roofline_pct": nemotron_held_gmm_roofline_pct,
           "conv_silu_fwd_roofline_pct": conv_silu_fwd_roofline_pct,
           "conv_silu_bwd_roofline_pct": conv_silu_bwd_roofline_pct}
CUT = ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
       "vocab_size"]
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def _cell():
    return harness.load_cell(CELL, ROOT)


# ------------------------------------------------------------ required work

def test_nemotron_train_flops_per_token_by_hand():
    c = _cell().config
    assert (c["hidden_size"], c["mamba_num_heads"], c["mamba_head_dim"],
            c["n_groups"], c["ssm_state_size"], c["conv_kernel"], c["chunk_size"],
            c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
            c["moe_intermediate_size"], c["moe_shared_expert_intermediate_size"],
            c["router_width"], c["n_routed_experts"], c["num_experts_per_tok"],
            c["vocab_size"], c["hybrid_override_pattern"]) \
        == (2688, 64, 64, 8, 128, 4, 128, 32, 2, 128, 1856, 3712, 128, 8, 6,
            16384, "MEMEM*EME")
    # operations a token, forward (a multiply-add is two)
    mamba = 4 * 2 * 2688 * (10_304 + 4096)          # in_proj and out_proj, 4 layers
    # a chunk of 128: C.B^T over the causal half a group, the plane against
    # dt x over it, the entering state against C and the chunk's own addition
    scan_layer = 2 * (8 * 128 * 128 // 2 + 64 * (128 * 64 // 2 + 2 * 128 * 64))
    assert scan_layer == 2_752_512
    projections = 2 * 2688 * (2 * 4096 + 2 * 256)   # q, o; k, v
    attention = 2 * 8192 * 4096                     # q.k^T and p.v over half the sequence
    router = 4 * 2 * 2688 * 128
    shared = 4 * 2 * 2 * 2688 * 3712
    held = 4 * 2 * 2 * 2688 * 1856 * (6 * 8 / 128)  # 3/8 of a held row a token
    head = 2 * 2688 * 16_384
    forward = (mamba + 4 * scan_layer + projections + attention + router
               + shared + held + head)
    assert forward == 714_981_376 and 3 * forward == 2_144_944_128
    assert flops_nemotron_h.train_flops_per_token(c, 8192) == 3 * forward
    parts = flops_nemotron_h.forward_flops_per_token(
        flops_nemotron_h.shape(c), 8192)
    assert sum(parts.values()) == forward
    # the shares the issue quotes: the Mamba-2 layers 45%, the expert layers
    # 27%, attention 16%, the head 12%; the scan alone 1.5%
    share = {k: 100 * v / forward for k, v in parts.items()}
    assert round(share["mamba_projections"] + share["scan"]) == 45
    assert round(share["router"] + share["shared_experts"]
                 + share["held_experts"]) == 27
    assert round(share["projections"] + share["attention"]) == 16
    assert round(share["head"]) == 12 and round(share["scan"], 1) == 1.5


def test_kernel_costs_by_hand_and_the_parts_sum_to_the_step():
    cell = _cell()
    parts = flops_nemotron_h.parts(cell.config, cell.traffic)
    assert set(parts) == {"ssd_fwd", "ssd_bwd", "conv_fwd", "conv_bwd",
                          "flash_fwd", "flash_bwd", "gmm", "xent"}
    # the scan, four layers of 8,192 positions: x and y [T, 4,096] and B, C
    # [T, 1,024] at two bytes, 64 chunks x 64 heads of a float32 [64, 128] state
    wide, narrow = 8192 * 4096 * 2, 8192 * 1024 * 2
    states = 64 * 64 * 64 * 128 * 4
    assert states == 134_217_728                    # the issue's 134 MB a layer
    product = 8192 * 2_752_512
    assert parts["ssd_fwd"] == flops.KernelCost(
        4 * product, 4 * (2 * wide + 2 * narrow + states))
    assert parts["ssd_bwd"] == flops.KernelCost(
        4 * 2 * product, 4 * (3 * wide + 4 * narrow + states))
    assert parts["ssd_fwd"].bound(V5E) == parts["ssd_bwd"].bound(V5E) == "memory"
    assert parts["ssd_fwd"].least_seconds(V5E) / 4 == pytest.approx(0.3687e-3, rel=1e-3)
    assert parts["ssd_bwd"].least_seconds(V5E) / 4 == pytest.approx(0.4916e-3, rel=1e-3)
    # the convolution before it: [T, 6,144] at two bytes read and written;
    # x and dy read and dx written; no product
    array = 8192 * 6144 * 2
    assert parts["conv_fwd"] == flops.KernelCost(0.0, 4 * 2 * array)
    assert parts["conv_bwd"] == flops.KernelCost(0.0, 4 * 3 * array)
    assert parts["conv_fwd"].least_seconds(V5E) / 4 == pytest.approx(0.2458e-3, rel=1e-3)
    # flash: 1 sequence, 32 query heads of 128 over the causal triangle, K and
    # V once for each of the 2 KV heads
    pairs = 2 * 1 * 32 * (8192 * 8193 // 2) * 128
    assert parts["flash_fwd"].flops == 2 * pairs
    assert parts["flash_bwd"].flops == 5 * pairs
    q_bytes, kv_bytes = 8192 * 32 * 128 * 2, 8192 * 2 * 128 * 2
    assert parts["flash_fwd"].hbm_bytes == 2 * q_bytes + 2 * kv_bytes
    assert parts["flash_bwd"].hbm_bytes == 4 * q_bytes + 4 * kv_bytes
    assert parts["flash_fwd"].bound(V5E) == "compute"
    # the experts held: 8,192 x 6 x 8 / 128 = 3,072 rows a layer on average,
    # two banks: six products where a gated expert has nine
    assert flops_afmoe.held_rows(8192, flops_nemotron_h.shape(cell.config)) == 3072
    assert parts["gmm"].flops == 4 * 6 * 2 * 3072 * 2688 * 1856
    rows_d, rows_w, bank = 3072 * 2688 * 2, 3072 * 1856 * 2, 8 * 2688 * 1856
    assert parts["gmm"].hbm_bytes == 4 * (
        2 * 2 * (rows_d + rows_w + bank * 2) + 2 * (rows_d + rows_w + bank * 4))
    assert parts["xent"].flops == flops.fused_xent_cost(
        rows=8192, d_model=2688, vocab_size=16_384).flops
    family = cell.load_module("families", "nemotron_h")
    built = family.build(cell.config, dict(cell.traffic, pool_batches=1), 0, 1,
                         abstract=True)
    total = built.kernel_cost_per_step
    assert total.flops == pytest.approx(sum(p.flops for p in parts.values()))
    assert total.hbm_bytes == pytest.approx(sum(p.hbm_bytes for p in parts.values()))
    assert built.train_flops_per_token == 2_144_944_128
    assert built.tokens_per_step == 8192
    # a step: 17.6 TFLOP required, 0.089 s at the chip's peak
    assert round(built.train_flops_per_token * built.tokens_per_step / 1e12, 1) == 17.6


def test_the_cut_has_the_parameters_the_configuration_file_counts():
    """Per layer its norm and one mixer: Mamba-2 (in_proj, the convolution and
    its bias, A_log, D, dt_bias, the gated norm, out_proj), attention (q, o,
    k, v) or the experts (router, bias, 8 routed of two banks, the shared
    one); embedding and untied head; the final norm."""
    import jax
    import numpy as np
    cell = _cell()
    family = cell.load_module("families", "nemotron_h")
    built = family.build(cell.config, dict(cell.traffic, pool_batches=1), 0, 1,
                         abstract=True)
    mamba = (2688 * (4096 + 6144 + 64) + 6144 * 4 + 6144 + 3 * 64 + 4096
             + 4096 * 2688 + 2688)
    attention = 2 * 2688 * 4096 + 2 * 2688 * 256 + 2688
    expert, shared = 2 * 2688 * 1856, 2 * 2688 * 3712
    experts = 8 * expert + shared + 2688 * 128 + 128 + 2688
    assert (mamba, attention, expert, shared, experts) == \
        (38_744_896, 23_399_040, 9_977_856, 19_955_712, 100_125_440)
    total = 4 * mamba + attention + 4 * experts + 2 * 16_384 * 2688 + 2688
    assert total == 666_963_456
    leaves = jax.tree_util.tree_leaves_with_path(built.params)
    assert sum(int(np.prod(x.shape)) for _, x in leaves) == total
    by_block = {i: sum(int(np.prod(x.shape)) for path, x in leaves
                       if path[0].key == f"block_{i}") for i in range(9)}
    assert [by_block[i] for i in range(9)] == [
        {"M": mamba, "E": experts, "*": attention}[kind] for kind in "MEMEM*EME"]
    assert {str(x.dtype) for _, x in leaves} == {"float32"}
    assert "666,963,456" in cell.config["reduced_why"]
    # 20 bytes a parameter on the chip (PERF.md §4): 12.42 GiB of 15.75
    assert 20 * total / 2**30 == pytest.approx(12.42, abs=0.005)
    assert 0.25 * V5E.hbm_bytes < 20 * total < V5E.hbm_bytes


# ------------------------------------------------------------- the readers

def _record(by_group, busy_s=1.0, steps=4, cell=None):
    device = types.SimpleNamespace(by_group=by_group, busy_s=busy_s)
    trace = types.SimpleNamespace(devices={0: device})
    return {"trace": trace, "trace_steps": steps, "peaks": V5E,
            "cell": cell or _cell()}


def test_new_readers_on_a_trace_made_by_hand():
    record = _record({"pallas:ssd_fwd": 0.016, "pallas:ssd_bwd": 0.02,
                      "pallas:conv_silu_fwd": 0.008, "pallas:conv_silu_bwd": 0.01,
                      "pallas:flash_fwd": 0.03, "pallas:flash_bwd_dkv": 0.05,
                      "pallas:moe_gmm_fwd": 0.02, "pallas:moe_gmm_bwd_dx": 0.02,
                      "pallas:moe_gmm_bwd_dw": 0.04, "pallas:xent_fwd": 0.1,
                      "fusion (kOutput)": 0.4})
    # 4 steps of 4 layers need 16 x 0.3687 ms of forward at the memory
    # bandwidth and took 16 ms; 16 x 0.4916 ms of backward and took 20 ms
    assert ssd_fwd_roofline_pct.read(record) == pytest.approx(36.87, rel=1e-3)
    assert ssd_bwd_roofline_pct.read(record) == pytest.approx(39.33, rel=1e-3)
    # 16 x 0.2458 ms of the convolution forward in 8 ms, 16 x 0.3687 back in 10
    assert conv_silu_fwd_roofline_pct.read(record) == pytest.approx(49.16, rel=1e-3)
    assert conv_silu_bwd_roofline_pct.read(record) == pytest.approx(58.99, rel=1e-3)
    assert ssd_time_pct.read(record) == pytest.approx(5.4)
    parts = flops_nemotron_h.parts(record["cell"].config, record["cell"].traffic)
    least = {k: v.least_seconds(V5E) for k, v in parts.items()}
    assert least["flash_fwd"] == pytest.approx(2.791e-3, rel=1e-3)
    assert nemotron_flash_fwd_roofline_pct.read(record) == \
        pytest.approx(100 * 4 * least["flash_fwd"] / 0.03)
    # the one-pass backward holds no flash_bwd_dq: the sum holds
    assert nemotron_flash_bwd_roofline_pct.read(record) == \
        pytest.approx(100 * 4 * least["flash_bwd"] / 0.05)
    assert nemotron_held_gmm_roofline_pct.read(record) == \
        pytest.approx(100 * 4 * least["gmm"] / 0.08)
    for reader in READERS.values():
        assert 0 < reader.read(record) <= 100


def test_new_readers_find_nothing_where_there_is_nothing_to_read(monkeypatch):
    # another family's cell, a run without a device trace, a checkout older
    # than the kernels' names, one older than the scan: nothing, and no raise
    groups = {"pallas:flash_fwd": 0.3, "pallas:ssd_fwd": 0.1}
    untraced = {"trace": None, "cell": _cell(), "peaks": V5E, "trace_steps": 4}
    for other in ("gpt2m-pretrain-1k", "trinity-pretrain-8k", "lfm2-pretrain-8k"):
        record = _record(groups, cell=harness.load_cell(other, ROOT))
        for reader in READERS.values():
            assert reader.read(record) is None
    for reader in READERS.values():
        assert reader.read(untraced) is None
    older = tuple(n for n in kernel_parts.program_kernel_names()
                  if not n.startswith(("ssd", "conv_silu")))
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
    assert work == {"name": CELL, "config": CONFIG, "traffic": "pretrain-8k",
                    "chips": 1, "why": work["why"]}
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == CUT
    assert entry["source"] == ("https://huggingface.co/nvidia/NVIDIA-Nemotron-3-"
                               "Nano-30B-A3B-BF16/blob/main/config.json")
    assert all(1 <= len(x[k]) <= 200 for x in (entry, work)
               for k in ("why", "source") if k in x)
    cell = _cell()
    for sub in ("families", "reference"):
        cell.find(sub, "nemotron_h.py")
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
    # the traffic is trinity-pretrain-8k's file, as it stands
    assert work["traffic"] == next(w for w in bench["workloads"]
                                   if w["name"] == "trinity-pretrain-8k")["traffic"]
    t = cell.traffic
    assert (t["seq_len"], t["micro_batch"], t["accumulation"], t["log_every"],
            t["pool_batches"], t["check_sequences"], t["strategy"], t["mesh"]) \
        == (8192, 1, 1, 8, 8, 1, "AllReduce", {"data": 1})


def test_the_configuration_keeps_every_published_number_but_the_four_cut():
    """Against the catalog's own ``config`` where the guide is installed; the
    cut, the deployment and every assumed fact are stated in the file."""
    config = _cell().config
    cut = {"num_hidden_layers": 9, "hybrid_override_pattern": "MEMEM*EME",
           "n_routed_experts": 8, "vocab_size": 16384}
    for key, value in cut.items():
        assert config[key] == value
    assert config["published"] == {
        "num_hidden_layers": 52, "hybrid_override_pattern": PUBLISHED_PATTERN,
        "n_routed_experts": 128, "vocab_size": 131072}
    assert [r.split()[0] for r in config["reduced"]] == CUT
    # the cut is the model's own first nine layers, and holds the published
    # 23 : 23 : 6 to the nearest whole period
    assert PUBLISHED_PATTERN.startswith(cut["hybrid_override_pattern"])
    assert [PUBLISHED_PATTERN.count(k) for k in "ME*"] == [23, 23, 6]
    assert [cut["hybrid_override_pattern"].count(k) for k in "ME*"] == [4, 4, 1]
    widths = dict(hidden_size=2688, mamba_num_heads=64, mamba_head_dim=64,
                  n_groups=8, ssm_state_size=128, conv_kernel=4, chunk_size=128,
                  num_attention_heads=32, num_key_value_heads=2, head_dim=128,
                  moe_intermediate_size=1856, intermediate_size=1856,
                  moe_shared_expert_intermediate_size=3712,
                  num_experts_per_tok=6, routed_scaling_factor=2.5,
                  norm_topk_prob=True, n_shared_experts=1,
                  mlp_hidden_act="relu2", use_conv_bias=True,
                  norm_eps=1e-5, layer_norm_epsilon=1e-5,
                  time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
                  rescale_prenorm_residual=True, tie_word_embeddings=False,
                  max_position_embeddings=262144, model_type="nemotron_h")
    for key, value in widths.items():
        assert config[key] == value, key
    assert config["family"] == "nemotron_h"
    assert config["router_width"] == 128 and config["first_expert_held"] == 0
    assert "16 chips" in config["deployment"] and "1/16" in config["deployment"]
    assumed = config["assumed"]
    assert (assumed["rows_bound"], assumed["route_eps"], assumed["ssm_impl"],
            assumed["attention_impl"], assumed["fused_head"], assumed["remat"],
            assumed["load_balance_coeff"]) == (6144, 1e-20, "pallas", "flash",
                                               True, True, 0.001)
    assert set(assumed) == {k for keys in config["assumed_why"]
                            for k in keys.split(", ")}
    assert config["departures"] and config["expects_pallas"] is True
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert row["source_url"] in config["source"]
    for key, value in row["config"].items():
        if key not in cut:
            assert config[key] == value, key
        else:
            assert config["published"][key] == value, key


# ----------------------------------------------------------- CPU rehearsal

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The shared scratch root plus a tiny Nemotron-H configuration and cell,
    as new files and entries: the three layer kinds, 2 heads a group and 2
    query heads a KV head, 2 of 8 experts held, chunks of 16."""
    root = scratch.make_root(tmp_path_factory.mktemp("nemotron_root"))
    with open(os.path.join(ROOT, "benchmark", "configs", f"{CONFIG}.json")) as f:
        config = json.load(f)
    config.update(hidden_size=128, mamba_num_heads=4, mamba_head_dim=16,
                  n_groups=2, ssm_state_size=16, chunk_size=16,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                  moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
                  num_hidden_layers=4, hybrid_override_pattern="ME*M",
                  router_width=8, n_routed_experts=2, first_expert_held=2,
                  num_experts_per_tok=2, vocab_size=503,
                  max_position_embeddings=64)
    config["assumed"] = dict(
        config["assumed"], rows_bound=8, learning_rate=0.003, warmup_steps=4,
        ssm_impl="xla",      # the kernels want a state and a group of 128 lanes
        expert_bias_balance={"first_coeff": 0.05, "iterations": 8})
    with open(os.path.join(root, "extra", "configs", "tiny-nemotron.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(ROOT, "benchmark", "traffic", "pretrain-8k.json")) as f:
        traffic = json.load(f)
    traffic.update(seq_len=40, micro_batch=2, log_every=2, check_sequences=2)
    with open(os.path.join(root, "extra", "traffic", "tiny-8k-n.json"), "w") as f:
        json.dump(traffic, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-nemotron", "source": "test only",
                             "reduced": [], "why": "test only",
                             "file": "extra/configs/tiny-nemotron.json"})
    bench["workloads"].append({"name": "tiny-nemotron-8k",
                               "config": "tiny-nemotron", "traffic": "tiny-8k-n",
                               "chips": 1, "why": "test only"})
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_tiny_nemotron_cell_end_to_end_on_the_cpu(root, trace):
    line = test_harness_cpu._rehearse(root, "tiny-nemotron-8k", devices=1,
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
