"""What PR 37 added, by new files only: Kanana-2-30B-A3B's required operations
and its kernels' operations and bytes against counts made by hand, the
parameter count of the cut, the four new readers on a trace made by hand,
``BENCHMARK.json``'s new entries, and a tiny ``deepseek_v3`` cell end to end
on the CPU through ``run_cell(require_tpu=False)``."""

import json
import os
import types

import pytest

from benchmark import (flops, flops_afmoe, flops_deepseek_v3, flops_moe,
                       harness, kernel_parts, peaks)
from benchmark.layers import (kanana_held_gmm_roofline_pct,
                              mla_flash_bwd_roofline_pct,
                              mla_flash_fwd_roofline_pct, mla_flash_time_pct)
from benchmark.tests import scratch, test_harness_cpu
from benchmark.tests.conftest import ROOT

V5E = peaks.peaks_for("TPU v5 lite")
CELL = "kanana-pretrain-16k"
CONFIG = "kanana-2-30b-a3b"
READERS = {"mla_flash_fwd_roofline_pct": mla_flash_fwd_roofline_pct,
           "mla_flash_bwd_roofline_pct": mla_flash_bwd_roofline_pct,
           "mla_flash_time_pct": mla_flash_time_pct,
           "kanana_held_gmm_roofline_pct": kanana_held_gmm_roofline_pct}
CUT = ["num_hidden_layers", "n_routed_experts", "vocab_size"]


def _cell():
    return harness.load_cell(CELL, ROOT)


# ------------------------------------------------------------ required work

def test_kanana_train_flops_per_token_by_hand():
    c = _cell().config
    assert (c["hidden_size"], c["num_attention_heads"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"],
            c["intermediate_size"], c["moe_intermediate_size"],
            c["n_shared_experts"], c["router_width"], c["n_routed_experts"],
            c["num_experts_per_tok"], c["vocab_size"], c["num_hidden_layers"],
            c["first_k_dense_replace"]) \
        == (2048, 32, 128, 64, 128, 512, 6144, 768, 2, 128, 8, 6, 16032, 6, 1)
    # operations a token, forward (a multiply-add is two)
    projections = 2 * (2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048)
    core = 16_384 * 32 * (192 + 128)        # 2 x half the sequence x heads x widths
    dense = 3 * 2 * 2048 * 6144
    shared = 3 * 2 * 2048 * 1536
    router = 2 * 2048 * 128
    held = 3 * 2 * 2048 * 768 * 6 * 8 / 128
    head = 2 * 2048 * 16_032
    assert (projections, core, dense, shared, router, held, head) == (
        52_690_944, 167_772_160, 75_497_472, 18_874_368, 524_288, 3_538_944,
        65_667_072)
    dense_layer = projections + core + dense
    expert_layer = projections + core + router + shared + held
    assert round(dense_layer / 1e6) == 296 and round(expert_layer / 1e6, 1) == 243.4
    forward = dense_layer + 5 * expert_layer + head
    assert forward == 1_578_631_168
    by_part = flops_deepseek_v3.forward_flops_per_token(
        flops_deepseek_v3.shape(c), 16_384)
    assert by_part == {"projections": 6 * projections, "attention": 6 * core,
                       "dense_mlp": dense, "router": 5 * router,
                       "shared_experts": 5 * shared, "held_experts": 5 * held,
                       "head": head}
    assert flops_deepseek_v3.train_flops_per_token(c, 16_384) == 3 * forward
    assert 3 * forward == 4_735_893_504                   # 4.74 GFLOP a token
    # latent attention is 91% of an expert layer's operations, its core 69%
    assert round(100 * (projections + core) / expert_layer) == 91
    assert round(100 * core / expert_layer) == 69


def test_kernel_costs_by_hand_and_the_parts_sum_to_the_step():
    cell = _cell()
    parts = flops_deepseek_v3.parts(cell.config, cell.traffic)
    assert set(parts) == {"flash_fwd", "flash_bwd", "gmm", "xent"}
    pairs = 16_384 * 16_385 // 2                          # the causal triangle
    assert flops_afmoe.band_pairs(16_384, None) == pairs
    fwd, bwd = flops_deepseek_v3.mla_flash_cost(
        batch=1, seq_len=16_384, n_heads=32, d_nope=128, d_rope=64, d_v=128)
    # forward 2 products (scores 192 deep, values 128), backward 5 (three 192
    # deep, two 128), a multiply-add two operations
    assert fwd.flops == 2 * 32 * pairs * (192 + 128)
    assert bwd.flops == 2 * 32 * pairs * (3 * 192 + 2 * 128)
    q, k_head, v = (16_384 * 32 * w * 2 for w in (192, 128, 128))
    k_shared = 16_384 * 64 * 2                            # once a layer, not a head
    assert fwd.hbm_bytes == q + k_head + k_shared + 2 * v        # + o
    assert bwd.hbm_bytes == 2 * (q + k_head + k_shared) + 4 * v  # v, o, dO, dv
    assert fwd.least_seconds(V5E) == pytest.approx(13.954e-3, rel=1e-3)
    assert bwd.least_seconds(V5E) == pytest.approx(36.28e-3, rel=1e-3)
    assert fwd.bound(V5E) == bwd.bound(V5E) == "compute"
    assert parts["flash_fwd"].flops == 6 * fwd.flops
    assert parts["flash_bwd"].hbm_bytes == 6 * bwd.hbm_bytes
    # the 8 held experts receive 16,384 x 6 x 8 / 128 rows on average
    rows = flops_afmoe.held_rows(16_384, flops_deepseek_v3.shape(cell.config))
    assert rows == 6144
    gmm = flops_moe.gmm_cost(rows=rows, d_model=2048, d_expert=768, n_experts=8)
    assert gmm.flops == 9 * 2 * 6144 * 2048 * 768
    assert parts["gmm"].flops == 5 * gmm.flops
    assert parts["xent"].flops == flops.fused_xent_cost(
        rows=16_384, d_model=2048, vocab_size=16_032).flops
    whole = flops_deepseek_v3.kernel_cost_per_step(cell.config, cell.traffic)
    assert whole.flops == pytest.approx(sum(p.flops for p in parts.values()))
    assert whole.hbm_bytes == pytest.approx(
        sum(p.hbm_bytes for p in parts.values()))
    # the step: 16,384 tokens x 4.74 GFLOP = 77.6 TFLOP required
    step = 16_384 * flops_deepseek_v3.train_flops_per_token(cell.config, 16_384)
    assert step == pytest.approx(77.6e12, rel=1e-3)


def test_the_cut_has_the_parameters_the_configuration_file_counts():
    """Per layer latent attention (q, kv-down, the latent's norm, kv-up, o),
    two norms, and the dense MLP or the experts (router, bias, 8 routed of
    three banks, the shared pair as one MLP); embedding and untied head; the
    final norm."""
    import jax
    import numpy as np
    cell = _cell()
    family = cell.load_module("families", "deepseek_v3")
    built = family.build(cell.config, dict(cell.traffic, pool_batches=1), 0, 1,
                         abstract=True)
    attention = 2048 * 6144 + 2048 * 576 + 512 + 512 * 8192 + 4096 * 2048
    expert, shared, dense = 3 * 2048 * 768, 3 * 2048 * 1536, 3 * 2048 * 6144
    dense_layer = attention + 2 * 2048 + dense
    expert_layer = attention + 2 * 2048 + 8 * expert + shared + 2048 * 128 + 128
    assert (attention, expert, shared, dense, dense_layer, expert_layer) == (
        26_345_984, 4_718_592, 9_437_184, 37_748_736, 64_098_816, 73_798_272)
    total = dense_layer + 5 * expert_layer + 2 * 16_032 * 2048 + 2048
    assert total == 498_759_296
    leaves = jax.tree_util.tree_leaves_with_path(built.params)
    assert sum(int(np.prod(x.shape)) for _, x in leaves) == total
    by_block = {i: sum(int(np.prod(x.shape)) for path, x in leaves
                       if path[0].key == f"block_{i}") for i in range(6)}
    assert [by_block[i] for i in range(6)] == [dense_layer] + 5 * [expert_layer]
    assert {str(x.dtype) for _, x in leaves} == {"float32"}
    assert "498,759,296" in cell.config["reduced_why"]
    # 20 bytes a parameter on the chip (PERF.md §4): 9.29 GiB of 15.75
    assert 20 * total / 2**30 == pytest.approx(9.29, abs=0.005)
    assert 0.25 * V5E.hbm_bytes < 20 * total < V5E.hbm_bytes
    # an uncut expert layer: 640M parameters, two do not fit a chip at 16 B
    whole = attention + 2 * 2048 + 128 * expert + shared + 2048 * 128 + 128
    assert round(whole / 1e6) == 640 and 2 * 16 * whole > V5E.hbm_bytes


# ------------------------------------------------------------- the readers

def _record(by_group, busy_s=1.0, steps=2, cell=None):
    device = types.SimpleNamespace(by_group=by_group, busy_s=busy_s)
    trace = types.SimpleNamespace(devices={0: device})
    return {"trace": trace, "trace_steps": steps, "peaks": V5E,
            "cell": cell or _cell()}


def test_new_readers_on_a_trace_made_by_hand():
    record = _record({"pallas:flash_fwd": 0.4, "pallas:flash_bwd_dkv": 0.5,
                      "pallas:flash_bwd_dq": 0.3, "pallas:moe_gmm_fwd": 0.004,
                      "pallas:moe_gmm_bwd_dx": 0.004, "pallas:moe_gmm_bwd_dw": 0.008,
                      "pallas:xent_fwd": 0.02, "fusion (kOutput)": 0.4},
                     busy_s=2.0)
    # 2 steps of 6 layers need 12 x 13.954 ms of forward and took 400 ms;
    # 12 x 36.28 ms of backward and took 500 + 300 under its two names
    assert mla_flash_fwd_roofline_pct.read(record) == pytest.approx(41.86, rel=1e-3)
    assert mla_flash_bwd_roofline_pct.read(record) == pytest.approx(54.42, rel=1e-3)
    assert mla_flash_time_pct.read(record) == pytest.approx(60.0)
    parts = flops_deepseek_v3.parts(record["cell"].config, record["cell"].traffic)
    assert kanana_held_gmm_roofline_pct.read(record) == pytest.approx(
        100 * 2 * parts["gmm"].least_seconds(V5E) / 0.016)
    for reader in READERS.values():
        assert 0 < reader.read(record) <= 100
    # one pass holds no flash_bwd_dq: the sum holds
    one_pass = _record({"pallas:flash_fwd": 0.4, "pallas:flash_bwd_dkv": 0.6})
    assert mla_flash_bwd_roofline_pct.read(one_pass) == pytest.approx(
        100 * 12 * 36.28e-3 / 0.6, rel=1e-3)


def test_new_readers_find_nothing_where_there_is_nothing_to_read(monkeypatch):
    # another family's cell, a run without a device trace, a checkout older
    # than the kernels' names: nothing, and no raise
    groups = {"pallas:flash_fwd": 0.3, "pallas:moe_gmm_fwd": 0.1}
    untraced = {"trace": None, "cell": _cell(), "peaks": V5E, "trace_steps": 4}
    for other in ("gpt2m-pretrain-1k", "trinity-pretrain-8k",
                  "nemotron-pretrain-8k"):
        record = _record(groups, cell=harness.load_cell(other, ROOT))
        for reader in READERS.values():
            assert reader.read(record) is None
    for reader in READERS.values():
        assert reader.read(untraced) is None
    monkeypatch.setattr(kernel_parts, "program_kernel_names", lambda: None)
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
    assert work == {"name": CELL, "config": CONFIG, "traffic": "pretrain-16k",
                    "chips": 1, "why": work["why"]}
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == CUT
    assert entry["source"] == ("https://huggingface.co/kakaocorp/kanana-2-30b-a3b-"
                               "instruct-2601/blob/main/config.json")
    assert all(1 <= len(x[k]) <= 200 for x in (entry, work)
               for k in ("why", "source") if k in x)
    cell = _cell()
    for sub in ("families", "reference"):
        cell.find(sub, "deepseek_v3.py")
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
    # no other cell runs this traffic: the first past 8,192 positions
    assert [w["name"] for w in bench["workloads"]
            if w["traffic"] == "pretrain-16k"] == [CELL]
    t = cell.traffic
    assert (t["seq_len"], t["micro_batch"], t["accumulation"], t["log_every"],
            t["pool_batches"], t["check_sequences"], t["strategy"], t["mesh"]) \
        == (16_384, 1, 1, 2, 8, 1, "AllReduce", {"data": 1})


def test_the_configuration_keeps_every_published_number_but_the_three_cut():
    """Against the catalog's own ``config`` where the guide is installed; the
    cut, the deployment and every assumed fact are stated in the file."""
    config = _cell().config
    cut = {"num_hidden_layers": 6, "n_routed_experts": 8, "vocab_size": 16032}
    for key, value in cut.items():
        assert config[key] == value
    assert config["published"] == {"num_hidden_layers": 48,
                                   "n_routed_experts": 128, "vocab_size": 128256}
    assert [r.split()[0] for r in config["reduced"]] == CUT
    assert 8 * config["vocab_size"] == config["published"]["vocab_size"]
    widths = dict(hidden_size=2048, num_attention_heads=32,
                  num_key_value_heads=32, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, qk_head_dim=192, v_head_dim=128,
                  head_dim=64, kv_lora_rank=512, q_lora_rank=None,
                  intermediate_size=6144, moe_intermediate_size=768,
                  n_shared_experts=2, num_experts_per_tok=6,
                  routed_scaling_factor=2.448, norm_topk_prob=True,
                  first_k_dense_replace=1, rope_theta=1_000_000,
                  rope_interleave=True, rope_scaling=None, rms_norm_eps=1e-6,
                  scoring_func="sigmoid", topk_method="noaux_tc",
                  tie_word_embeddings=False, max_position_embeddings=32768,
                  model_type="deepseek_v3")
    for key, value in widths.items():
        assert config[key] == value, key
    assert config["family"] == "deepseek_v3"
    assert config["router_width"] == 128 and config["first_expert_held"] == 0
    assert "16 chips" in config["deployment"] and "1/16" in config["deployment"]
    assumed = config["assumed"]
    assert (assumed["rows_bound"], assumed["route_eps"], assumed["attention_impl"],
            assumed["fused_head"], assumed["remat"], assumed["load_balance_coeff"]) \
        == (12288, 1e-20, "flash", True, True, 0.001)
    assert set(assumed) == {k for keys in config["assumed_why"]
                            for k in keys.split(", ")}
    assert config["departures"] and config["expects_pallas"] is True
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    assert row["source_url"] in config["source"]
    for key, value in row["config"].items():
        if key not in cut:
            assert config[key] == value, key
        else:
            assert config["published"][key] == value, key


# ----------------------------------------------------------- CPU rehearsal

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The shared scratch root plus a tiny latent-attention configuration and
    cell, as new files and entries: a dense layer and two expert layers, keys
    48 wide (32 + 16 shared) over values 32, 2 of 8 experts held."""
    root = scratch.make_root(tmp_path_factory.mktemp("kanana_root"))
    with open(os.path.join(ROOT, "benchmark", "configs", f"{CONFIG}.json")) as f:
        config = json.load(f)
    config.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=4,
                  qk_nope_head_dim=32, qk_rope_head_dim=16, qk_head_dim=48,
                  head_dim=16, v_head_dim=32, kv_lora_rank=48,
                  intermediate_size=96, moe_intermediate_size=32,
                  num_hidden_layers=3, router_width=8, n_routed_experts=2,
                  first_expert_held=2, num_experts_per_tok=2, vocab_size=503,
                  max_position_embeddings=64)
    config["assumed"] = dict(
        config["assumed"], rows_bound=8, learning_rate=0.003, warmup_steps=4,
        expert_bias_balance={"first_coeff": 0.05, "iterations": 8})
    with open(os.path.join(root, "extra", "configs", "tiny-kanana.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(ROOT, "benchmark", "traffic", "pretrain-16k.json")) as f:
        traffic = json.load(f)
    traffic.update(seq_len=40, micro_batch=2, check_sequences=2)
    with open(os.path.join(root, "extra", "traffic", "tiny-16k.json"), "w") as f:
        json.dump(traffic, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-kanana", "source": "test only",
                             "reduced": [], "why": "test only",
                             "file": "extra/configs/tiny-kanana.json"})
    bench["workloads"].append({"name": "tiny-kanana-16k",
                               "config": "tiny-kanana", "traffic": "tiny-16k",
                               "chips": 1, "why": "test only"})
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_tiny_kanana_cell_end_to_end_on_the_cpu(root, trace):
    line = test_harness_cpu._rehearse(root, "tiny-kanana-16k", devices=1,
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
