"""What PR 46 added, by new files only: MiMo-V2.5's required operations and
its kernels' operations and bytes against counts made by hand, the parameter
count of the cut through ``init_params``, the nine new readers on a trace made
by hand, ``BENCHMARK.json``'s new entries (found by name, wherever later PRs
put theirs), the configuration against the catalog's row, and a tiny
``mimo_v2`` cell end to end on four virtual CPU devices through
``run_cell(require_tpu=False)`` under ``FullySharded``, the expert banks
stored as quarters and the balancing rule run on them before the first
step."""

import json
import os
import types

import pytest

from benchmark import flops, flops_afmoe, flops_mimo_v2, flops_moe, harness, \
    kernel_parts, peaks, program_counters
from benchmark.layers import (mimo_full_flash_bwd_roofline_pct,
                              mimo_full_flash_fwd_roofline_pct,
                              mimo_held_gmm_roofline_pct,
                              mimo_param_gather_ms_per_step,
                              mimo_swa_flash_bwd_roofline_pct,
                              mimo_swa_flash_fwd_roofline_pct,
                              mimo_swa_flash_time_pct, mimo_swa_tile_fill_pct,
                              mimo_xent_roofline_pct)
from benchmark.tests import scratch, test_harness_cpu
from benchmark.tests.conftest import ROOT

V5E = peaks.peaks_for("TPU v5 lite")
CELL = "mimo-sharded4-8k"
CONFIG = "mimo-v2.5"
READERS = {"mimo_swa_flash_fwd_roofline_pct": mimo_swa_flash_fwd_roofline_pct,
           "mimo_swa_flash_bwd_roofline_pct": mimo_swa_flash_bwd_roofline_pct,
           "mimo_swa_flash_time_pct": mimo_swa_flash_time_pct,
           "mimo_full_flash_fwd_roofline_pct": mimo_full_flash_fwd_roofline_pct,
           "mimo_full_flash_bwd_roofline_pct": mimo_full_flash_bwd_roofline_pct,
           "mimo_swa_tile_fill_pct": mimo_swa_tile_fill_pct,
           "mimo_held_gmm_roofline_pct": mimo_held_gmm_roofline_pct,
           "mimo_xent_roofline_pct": mimo_xent_roofline_pct,
           "mimo_param_gather_ms_per_step": mimo_param_gather_ms_per_step}
SHARES = [name for name in READERS if name.endswith("roofline_pct")]
REDUCED = ["num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
           "n_routed_experts", "vocab_size"]


def _cell():
    return harness.load_cell(CELL, ROOT)


# ------------------------------------------------------------ required work

def test_mimo_train_flops_per_token_by_hand():
    """Forward, a token, at 8,192 positions: two full and five sliding
    layers' projections, the band's pairs at 192 + 128, the dense MLP, six
    routers, a quarter of an expert a token a layer, the sliced head."""
    cell = _cell()
    s = flops_mimo_v2.shape(cell.config)
    assert (s["n_sliding"], s["n_full"], s["n_dense"]) == (5, 2, 1)
    parts = flops_mimo_v2.forward_flops_per_token(s, 8192)
    full = 4096 * 12288 + 4096 * 768 + 4096 * 512 + 8192 * 4096
    sliding = 4096 * 12288 + 4096 * 1536 + 4096 * 1024 + 8192 * 4096
    assert (full, sliding) == (89_128_960, 94_371_840)
    pairs = 5 * (128 * 129 // 2 + (8192 - 128) * 128) + 2 * (8192 * 8193 // 2)
    assert parts == {
        "projections": 2 * (2 * full + 5 * sliding),
        "attention": 2 * 64 * 320 * pairs / 8192,
        "dense_mlp": 2 * 3 * 4096 * 16384,
        "router": 6 * 2 * 4096 * 256,
        "held_experts": 6 * 2 * 3 * 4096 * 2048 * 8 * 8 / 256,
        "head": 2 * 4096 * 19072}
    forward = sum(parts.values())
    assert forward == pytest.approx(2.309e9, rel=1e-3)
    share = {k: v / forward for k, v in parts.items()}
    assert share["projections"] == pytest.approx(0.563, abs=1e-3)
    assert share["attention"] == pytest.approx(0.157, abs=1e-3)
    assert share["dense_mlp"] == pytest.approx(0.174, abs=1e-3)
    assert share["held_experts"] == pytest.approx(0.033, abs=1e-3)
    assert flops_mimo_v2.train_flops_per_token(cell.config, 8192) == 3.0 * forward


def test_kernel_costs_by_hand_and_the_parts_sum_to_the_step():
    cell = _cell()
    swa_f, swa_b = flops_mimo_v2.two_width_flash_cost(
        batch=4, seq_len=8192, n_heads=64, n_kv_heads=8, head_dim=192,
        v_head_dim=128, window=128)
    pairs = 128 * 129 // 2 + (8192 - 128) * 128
    assert pairs == flops_afmoe.band_pairs(8192, 128) == 1_040_448
    assert swa_f.flops == 2 * 4 * 64 * pairs * 320
    assert swa_b.flops == 2 * 4 * 64 * pairs * (3 * 192 + 2 * 128)
    rows = 4 * 8192 * 2
    moved = rows * (64 * 192 + 8 * 192 + 8 * 128 + 64 * 128)
    assert (swa_f.hbm_bytes, swa_b.hbm_bytes) == (moved, 2 * moved)
    # the window leaves both sliding kernels memory-bound by what they must
    # do: 128 keys a query are 10.7 GFLOP a head-width of 320 beside 94 MB
    assert swa_f.bound(V5E) == "memory" and swa_b.bound(V5E) == "memory"
    full_f, _ = flops_mimo_v2.two_width_flash_cost(
        batch=4, seq_len=8192, n_heads=64, n_kv_heads=4, head_dim=192,
        v_head_dim=128, window=None)
    assert full_f.flops == 2 * 4 * 64 * (8192 * 8193 // 2) * 320
    assert full_f.bound(V5E) == "compute"
    parts = flops_mimo_v2.parts(cell.config, cell.traffic)
    assert set(parts) == {"swa_flash_fwd", "swa_flash_bwd", "full_flash_fwd",
                          "full_flash_bwd", "gmm", "xent"}
    assert parts["swa_flash_fwd"].flops == 5 * swa_f.flops
    assert parts["full_flash_fwd"].flops == 2 * full_f.flops
    # a chip's 2,048 held rows against its own copy of the banks, four chips
    gmm = flops_moe.gmm_cost(rows=2048, d_model=4096, d_expert=2048, n_experts=8)
    assert parts["gmm"].flops == pytest.approx(6 * 4 * gmm.flops)
    assert parts["gmm"].hbm_bytes == pytest.approx(6 * 4 * gmm.hbm_bytes)
    assert gmm.bound(V5E) == "memory"        # 256 rows an expert: the banks' bytes
    xent = flops.fused_xent_cost(rows=8192, d_model=4096, vocab_size=19072)
    assert parts["xent"].flops == pytest.approx(4 * xent.flops)
    total = flops_mimo_v2.kernel_cost_per_step(cell.config, cell.traffic)
    assert total.flops == pytest.approx(sum(p.flops for p in parts.values()))
    assert total.hbm_bytes == pytest.approx(sum(p.hbm_bytes for p in parts.values()))
    family = cell.load_module("families", "mimo_v2")
    built = family.build(cell.config, dict(cell.traffic, pool_batches=1), 0, 4,
                         abstract=True)
    assert built.kernel_cost_per_step == total
    assert built.tokens_per_step == 4 * 8192


def test_the_cut_has_the_parameters_the_issue_counts_through_init_params():
    """Layer 0 (full, dense), five sliding expert layers and a full one with
    8 experts held, an eighth of the vocabulary twice, the final norm; a
    quarter a chip of every large leaf."""
    import jax
    import numpy as np
    from autodist_tpu.strategy.partition_utils import data_shard_axis
    cell = _cell()
    family = cell.load_module("families", "mimo_v2")
    built = family.build(cell.config, dict(cell.traffic, pool_batches=1), 0, 4,
                         abstract=True)
    full, sliding = 89_128_960, 94_371_904          # the sliding one with 64 sinks
    experts = 8 * 3 * 4096 * 2048 + 4096 * 256 + 256
    layer0 = full + 8192 + 3 * 4096 * 16384
    assert (layer0, sliding + 8192 + experts, full + 8192 + experts) == \
        (290_463_744, 296_755_520, 291_512_576)
    total = layer0 + 5 * 296_755_520 + 291_512_576 + 2 * 19072 * 4096 + 4096
    assert total == 2_221_995_840
    leaves = jax.tree_util.tree_leaves_with_path(built.params)
    count = lambda ls: sum(int(np.prod(x.shape)) for _, x in ls)  # noqa: E731
    assert count(leaves) == total
    by_block = [count([(p, x) for p, x in leaves if p[0].key == f"block_{i}"])
                for i in range(7)]
    assert by_block == [layer0] + [296_755_520] * 5 + [291_512_576]
    assert {str(x.dtype) for _, x in leaves} == {"float32"}
    assert "2,221,995,840" in cell.config["reduced_why"]
    # the banks are stored as two experts a chip, and what stays whole on
    # every chip (norms, sinks, biases) is a ten-thousandth
    shapes = {tuple(x.shape) for _, x in leaves}
    assert data_shard_axis((8, 4096, 2048), 4) == 0 and (8, 4096, 2048) in shapes
    whole = count([(p, x) for p, x in leaves
                   if data_shard_axis(x.shape, 4) is None])
    assert whole == 15 * 4096 + 5 * 64 + 6 * 256
    # 16 bytes a parameter in the step's state + 4 in the caller's copy, a
    # quarter a chip: 10.35 GiB of 15.75 before an activation
    assert 20 * total / 4 / 2**30 == pytest.approx(10.35, abs=0.01)
    assert 16 * total > 2 * V5E.hbm_bytes        # two chips do not hold it whole


# ------------------------------------------------------------- the readers

def _record(by_group, busy_s=1.0, steps=2, cell=None, chips=4):
    devices = {i: types.SimpleNamespace(by_group=dict(by_group), busy_s=busy_s)
               for i in range(chips)}
    return {"trace": types.SimpleNamespace(devices=devices),
            "trace_steps": steps, "peaks": V5E, "cell": cell or _cell()}


GROUPS = {"pallas:flash_sink_fwd": 0.05, "pallas:flash_sink_bwd_dkv": 0.1,
          "pallas:flash_fwd": 0.05, "pallas:flash_bwd_dkv": 0.12,
          "pallas:moe_gmm_fwd": 0.01, "pallas:moe_gmm_bwd_dx": 0.01,
          "pallas:moe_gmm_bwd_dw": 0.02, "pallas:xent_fwd": 0.02,
          "pallas:xent_bwd_dw": 0.05, "all-gather": 0.04,
          "all-gather-start": 0.002, "all-reduce": 0.05,
          "collective-permute-done": 0.5, "fusion (kOutput)": 1.0}


def test_new_readers_on_a_trace_made_by_hand(monkeypatch):
    record = _record(GROUPS)
    parts = flops_mimo_v2.parts(record["cell"].config, record["cell"].traffic)
    least = {k: v.least_seconds(V5E) for k, v in parts.items()}
    # every chip's seconds in the denominator: 4 chips x 0.05 s for 2 steps
    assert mimo_swa_flash_fwd_roofline_pct.read(record) == \
        pytest.approx(100 * 2 * least["swa_flash_fwd"] / 0.2)
    assert mimo_swa_flash_bwd_roofline_pct.read(record) == \
        pytest.approx(100 * 2 * least["swa_flash_bwd"] / 0.4)
    assert mimo_full_flash_fwd_roofline_pct.read(record) == \
        pytest.approx(100 * 2 * least["full_flash_fwd"] / 0.2)
    assert mimo_full_flash_bwd_roofline_pct.read(record) == \
        pytest.approx(100 * 2 * least["full_flash_bwd"] / 0.48)
    assert mimo_held_gmm_roofline_pct.read(record) == \
        pytest.approx(100 * 2 * least["gmm"] / 0.16)
    assert mimo_xent_roofline_pct.read(record) == \
        pytest.approx(100 * 2 * least["xent"] / 0.28)
    assert mimo_swa_flash_time_pct.read(record) == pytest.approx(15.0)
    # a chip, a step: the all-gathers' 0.042 s over 2
    assert mimo_param_gather_ms_per_step.read(record) == pytest.approx(21.0)
    for name in SHARES:
        assert 0 < READERS[name].read(record) <= 100, name
    # a sliding layer's forward a chip: 0.46 ms of bytes (q, k, v, o once),
    # 0.22 ms of products at the peak: a few percent of 25 ms
    assert least["swa_flash_fwd"] / (4 * 5) == pytest.approx(0.46e-3, rel=0.02)
    assert mimo_swa_flash_fwd_roofline_pct.read(record) < 10
    # the tile fill is the program's own two gauges
    values = {"attn.band_pairs_visible": 5 * 256 * 1_040_448,
              "attn.band_pairs_computed": 5 * 256 * 31 * 512 * 512}
    monkeypatch.setattr(program_counters, "value", values.get)
    assert mimo_swa_tile_fill_pct.read(record) == pytest.approx(12.803, abs=1e-3)


def test_new_readers_find_nothing_where_there_is_nothing_to_read(monkeypatch):
    # another family's cell, a run without a device trace, a checkout older
    # than the sink's kernels (the parent of this PR): nothing, and no raise
    untraced = {"trace": None, "cell": _cell(), "peaks": V5E, "trace_steps": 4}
    for other in ("gpt2m-dp4-sync", "trinity-pretrain-8k", "jamba2-sharded4-16k"):
        record = _record(GROUPS, cell=harness.load_cell(other, ROOT))
        for reader in READERS.values():
            assert reader.read(record) is None
    monkeypatch.setattr(program_counters, "value", lambda name: None)
    for reader in READERS.values():
        assert reader.read(untraced) is None
    assert mimo_swa_tile_fill_pct.read(_record(GROUPS)) is None   # no gauge
    older = tuple(n for n in kernel_parts.program_kernel_names()
                  if not n.startswith("flash_sink"))
    for names in (None, older):
        monkeypatch.setattr(kernel_parts, "program_kernel_names", lambda: names)
        for name in SHARES + ["mimo_swa_flash_time_pct"]:
            assert READERS[name].read(_record(GROUPS)) is None


def test_named_kernels_missing_from_the_trace_fail_the_run():
    for name in SHARES:
        with pytest.raises(harness.BenchmarkError, match="no time under"):
            READERS[name].read(_record({"pallas:jvp__": 0.2}))
    # a trace without a collective of the kind reads 0, not nothing
    assert mimo_param_gather_ms_per_step.read(_record({"all-reduce": 0.1})) == 0.0


# ------------------------------------------------------ BENCHMARK.json

def test_new_entries_name_files_that_exist_and_cut_what_the_issue_names():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by name, not by place: later PRs append theirs
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert work == {"name": CELL, "config": CONFIG, "traffic": "sharded4-8k",
                    "chips": 4, "why": work["why"]}
    assert "attention sees more" in work["why"]
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == REDUCED
    assert entry["source"].startswith(
        "https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json")
    assert all(1 <= len(x[k]) <= 200 for x in (entry, work)
               for k in ("why", "source") if k in x)
    cell = _cell()
    for sub in ("families", "reference"):
        cell.find(sub, "mimo_v2.py")
    new = {m["name"]: m for m in bench["per_layer"] if m["name"] in READERS}
    assert set(new) == set(READERS)
    for name, m in new.items():
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s_per_chip"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        if name.endswith("ms_per_step"):
            assert (m["layer"], m["unit"], m["better"], m["source"]) == \
                ("sharding", "ms", "lower", "device_trace")
        else:
            assert (m["layer"], m["unit"]) == ("kernels", "%")
            assert m["better"] == ("lower" if name.endswith("time_pct") else "higher")
            assert m["source"] == ("program_counter" if name.endswith("fill_pct")
                                   else "device_trace")
        assert callable(cell.load_module("layers", name).read)
    # one cell in four may take four chips: the third slot opens at twelve
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= \
        len(bench["workloads"]) // 4
    # the traffic file is the issue's parameters and nothing else but a note
    assert {k: v for k, v in cell.traffic.items() if k != "note"} == {
        "job": "train", "chips": 4, "mesh": {"data": 4},
        "strategy": "FullySharded", "seq_len": 8192, "micro_batch": 1,
        "accumulation": 1, "log_every": 4, "pool_batches": 8,
        "check_sequences": 1}
    from autodist_tpu import strategy as strategies
    assert issubclass(getattr(strategies, cell.traffic["strategy"]),
                      strategies.StrategyBuilder)


def test_the_configuration_keeps_every_published_number_but_the_cut():
    """Against the catalog's own ``config`` where the guide is installed:
    every key verbatim, or under ``reduced`` with the published value stated;
    the deployment and every assumed fact are in the file."""
    config = _cell().config
    assert [r.split()[0] for r in config["reduced"]] == \
        ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"], config["router_width"]) == (7, 8, 19072, 256)
    assert config["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 1, 0]
    assert config["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    assert config["vocab_size"] * 8 == 152576 and config["vocab_size"] % 128 == 0
    widths = dict(hidden_size=4096, intermediate_size=16384,
                  moe_intermediate_size=2048, num_attention_heads=64,
                  num_key_value_heads=4, swa_num_key_value_heads=8,
                  head_dim=192, v_head_dim=128, swa_head_dim=192,
                  swa_v_head_dim=128, num_experts_per_tok=8, sliding_window=128,
                  partial_rotary_factor=0.334, attention_value_scale=0.707,
                  rope_theta=10000000, swa_rope_theta=10000,
                  layernorm_epsilon=1e-5, model_type="mimo_v2")
    for key, value in widths.items():
        assert config[key] == value, key
    assert config["family"] == "mimo_v2" and config["expects_pallas"] is True
    for said in ("32-way expert-parallel", "quarters", "1/32", "41 layers"):
        assert said in config["deployment"], said
    assumed = config["assumed"]
    assert (assumed["attention_impl"], assumed["fused_head"], assumed["remat"],
            assumed["rows_bound"], assumed["optimizer"], assumed["warmup_steps"],
            assumed["learning_rate"]) == ("flash", True, True, 4096, "adamw",
                                          10000, 3e-4)
    assert set(assumed) - {"expert_bias_balance"} >= {
        "sink", "window", "rotary_columns", "qk_norm_and_bias"}
    assert any("multi-token-prediction" in d for d in config["departures"])
    family = _cell().load_module("families", "mimo_v2")
    for key, _ in family.COMPUTED + family.BOTH_KINDS:
        with pytest.raises(ValueError, match=key):
            family.model_config(dict(config, **{key: "other"}))
    cfg = family.model_config(config)
    assert (cfg.rotary_dim, cfg.n_layers, cfg.experts_held,
            cfg.n_experts_routed) == (64, 7, 8, 256)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiMo-V2.5")
    assert row["source_url"] in config["source"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert key in config["published"], key
            if not isinstance(value, list):
                assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    # the cut keeps the published layers 0 and 6-11
    pattern, freq = (row["config"][k] for k in ("hybrid_layer_pattern",
                                                "moe_layer_freq"))
    assert config["hybrid_layer_pattern"] == pattern[:1] + pattern[6:12]
    assert config["moe_layer_freq"] == freq[:1] + freq[6:12]


# ----------------------------------------------------------- CPU rehearsal

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The shared scratch root plus a tiny MiMo-V2 configuration and a cell
    on four devices under ``FullySharded``, as new files and entries: a full
    dense layer, a sliding and a full expert layer, banks of 8 x 512 x 256
    (2^20 elements: stored as quarters)."""
    root = scratch.make_root(tmp_path_factory.mktemp("mimo_root"))
    with open(os.path.join(ROOT, "benchmark", "configs", f"{CONFIG}.json")) as f:
        config = json.load(f)
    config.update(hidden_size=512, intermediate_size=512,
                  moe_intermediate_size=256, num_attention_heads=8,
                  swa_num_attention_heads=8, num_key_value_heads=1,
                  swa_num_key_value_heads=2, head_dim=48, swa_head_dim=48,
                  v_head_dim=32, swa_v_head_dim=32, sliding_window=16,
                  sliding_window_size=16, num_hidden_layers=3,
                  hybrid_layer_pattern=[0, 1, 0], moe_layer_freq=[0, 1, 1],
                  router_width=32, num_experts_per_tok=4, vocab_size=1024,
                  max_position_embeddings=64)
    config["assumed"] = dict(
        config["assumed"], learning_rate=0.003, rows_bound=64,
        expert_bias_balance={"first_coeff": 0.05, "iterations": 4})
    with open(os.path.join(root, "extra", "configs", "tiny-mimo.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(ROOT, "benchmark", "traffic", "sharded4-8k.json")) as f:
        traffic = json.load(f)
    traffic.update(seq_len=48, micro_batch=1, log_every=2, check_sequences=1)
    with open(os.path.join(root, "extra", "traffic", "tiny-sharded4-8k.json"),
              "w") as f:
        json.dump(traffic, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-mimo", "source": "test only",
                             "reduced": [], "why": "test only",
                             "file": "extra/configs/tiny-mimo.json"})
    bench["workloads"].append({"name": "tiny-mimo-sharded4",
                               "config": "tiny-mimo",
                               "traffic": "tiny-sharded4-8k",
                               "chips": 4, "why": "test only"})
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_tiny_mimo_cell_end_to_end_on_four_virtual_devices(root, trace):
    """A seed past 2**31, as the driver's are. Every matrix and the expert
    banks are stored as quarters, the balancing rule runs on them first, the
    check's jit has no mesh in scope and the family's loss brings its own."""
    line = test_harness_cpu._rehearse(root, "tiny-mimo-sharded4", devices=4,
                                      trace=trace, seed=3000000019, seconds=8.0)
    test_harness_cpu._check_shape(line, 4)
    reference = line["checks"]["reference"]
    # bfloat16 sublayers at widths of 32 to 512: the job's own limits hold
    assert reference["loss_rel_diff"] < 2e-3 and reference["grad_rel_l2"] < 4.5e-2
    compiled = line["checks"]["compiled"]
    assert compiled["param_device_set_sizes"] == [4]
    assert "all-gather" in compiled["collectives"]
    if trace:
        # no device trace on the CPU: only the program's own count reads
        assert set(line["metrics"]) & set(READERS) <= {"mimo_swa_tile_fill_pct"}
        assert "compiled_hbm_gib" in line["metrics"]
    else:
        assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
