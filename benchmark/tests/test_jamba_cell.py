"""What PR 43 added, by new files only: AI21-Jamba2-3B's required operations
and its kernels' bytes against counts made by hand, the parameter count of the
cut through ``init_params``, the nine new readers on a trace made by hand,
``BENCHMARK.json``'s new entries (found by name, wherever later PRs put
theirs), the configuration against the catalog's row, and a tiny ``jamba``
cell end to end on four virtual CPU devices through
``run_cell(require_tpu=False)`` under ``FullySharded``."""

import json
import os
import types

import pytest

from benchmark import flops, flops_afmoe, flops_jamba, harness, kernel_parts, peaks
from benchmark.layers import (jamba_conv_silu_bwd_roofline_pct,
                              jamba_conv_silu_fwd_roofline_pct,
                              jamba_flash_bwd_roofline_pct,
                              jamba_flash_fwd_roofline_pct,
                              jamba_xent_roofline_pct,
                              param_gather_ms_per_step,
                              selective_scan_bwd_roofline_pct,
                              selective_scan_fwd_roofline_pct,
                              selective_scan_time_pct)
from benchmark.tests import scratch, test_harness_cpu
from benchmark.tests.conftest import ROOT

V5E = peaks.peaks_for("TPU v5 lite")
CELL = "jamba2-sharded4-16k"
CONFIG = "jamba2-3b"
READERS = {"selective_scan_fwd_roofline_pct": selective_scan_fwd_roofline_pct,
           "selective_scan_bwd_roofline_pct": selective_scan_bwd_roofline_pct,
           "selective_scan_time_pct": selective_scan_time_pct,
           "jamba_conv_silu_fwd_roofline_pct": jamba_conv_silu_fwd_roofline_pct,
           "jamba_conv_silu_bwd_roofline_pct": jamba_conv_silu_bwd_roofline_pct,
           "jamba_flash_fwd_roofline_pct": jamba_flash_fwd_roofline_pct,
           "jamba_flash_bwd_roofline_pct": jamba_flash_bwd_roofline_pct,
           "jamba_xent_roofline_pct": jamba_xent_roofline_pct,
           "param_gather_ms_per_step": param_gather_ms_per_step}
SHARES = [name for name in READERS if name.endswith("roofline_pct")]


def _cell():
    return harness.load_cell(CELL, ROOT)


# ------------------------------------------------------------ required work

def test_jamba_train_flops_per_token_by_hand():
    """Forward, a token, at 16,384 positions: 13 mixers of four projections
    and the recurrence, the attention layer, 14 MLPs, the tied head."""
    cell = _cell()
    s = flops_jamba.shape(cell.config)
    assert (s["n_mamba"], s["n_attention"], s["d_inner"], s["head_dim"]) == \
        (13, 1, 5120, 128)
    parts = flops_jamba.forward_flops_per_token(s, 16384)
    mixer = 2 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560)
    assert mixer == 82_247_680
    assert parts == {
        "mamba_projections": 13 * mixer, "scan": 13 * 2 * 3 * 5120 * 16,
        "projections": 2 * 2560 * (2 * 2560 + 2 * 128),
        "attention": 2 * 16384 * 2560, "mlp": 14 * 2 * 3 * 2560 * 8192,
        "head": 2 * 2560 * 65536}
    forward = sum(parts.values())
    assert forward == pytest.approx(3.284e9, rel=1e-3)
    share = {k: v / forward for k, v in parts.items()}
    assert share["mlp"] == pytest.approx(0.537, abs=1e-3)
    assert share["mamba_projections"] + share["scan"] == pytest.approx(0.328, abs=1e-3)
    assert share["head"] == pytest.approx(0.102, abs=1e-3)
    assert share["projections"] + share["attention"] == pytest.approx(0.034, abs=1e-3)
    assert share["scan"] < 0.01
    assert flops_jamba.train_flops_per_token(cell.config, 16384) == 3.0 * forward


def test_kernel_costs_by_hand_and_the_parts_sum_to_the_step():
    cell = _cell()
    s = flops_jamba.shape(cell.config)
    tokens = 4 * 16384
    fwd, bwd = flops_jamba.selective_scan_cost(tokens=tokens, s=s)
    wide = tokens * 5120
    states = (tokens // 128) * 5120 * 16 * 4
    assert (fwd.flops, bwd.flops) == (0.0, 0.0)        # nothing for the MXU
    assert fwd.hbm_bytes == wide * (2 + 2 + 4) + 2 * tokens * 16 * 4 + states
    assert bwd.hbm_bytes == wide * (3 * 2 + 2 * 4) + 4 * tokens * 16 * 4 + states
    assert fwd.bound(V5E) == bwd.bound(V5E) == "memory"
    # a chip's share of one layer's forward: 0.87 ms at 819 GB/s
    assert fwd.least_seconds(V5E) / 4 == pytest.approx(0.873e-3, rel=1e-2)
    conv_f, conv_b = flops_jamba.conv_cost(tokens=tokens, s=s)
    assert (conv_f.hbm_bytes, conv_b.hbm_bytes) == (2 * wide * 2, 3 * wide * 2)
    parts = flops_jamba.parts(cell.config, cell.traffic)
    assert parts["scan_fwd"].hbm_bytes == 13 * fwd.hbm_bytes
    assert parts["conv_bwd"].hbm_bytes == 13 * conv_b.hbm_bytes
    flash_f, flash_b = flops_afmoe.band_flash_cost(
        batch=4, seq_len=16384, n_heads=20, n_kv_heads=1, head_dim=128,
        window=None)
    assert parts["flash_fwd"] == flash_f and parts["flash_bwd"] == flash_b
    assert parts["xent"] == flops.fused_xent_cost(rows=tokens, d_model=2560,
                                                 vocab_size=65536)
    total = flops_jamba.kernel_cost_per_step(cell.config, cell.traffic)
    assert total.flops == pytest.approx(sum(p.flops for p in parts.values()))
    assert total.hbm_bytes == pytest.approx(sum(p.hbm_bytes for p in parts.values()))


def test_the_cut_has_the_parameters_the_issue_counts_through_init_params():
    """One whole period with the whole vocabulary: 13 Mamba-1 layers (mixer
    41,241,792 + MLP 62,914,560 + two norms), the attention layer, the tied
    table, the final norm; a quarter a chip of every large leaf."""
    import jax
    import numpy as np
    from autodist_tpu.strategy.partition_utils import data_shard_axis
    cell = _cell()
    family = cell.load_module("families", "jamba")
    built = family.build(cell.config, dict(cell.traffic, pool_batches=1), 0, 4,
                         abstract=True)
    mamba = 41_241_792 + 62_914_560 + 2 * 2560
    attention = 2 * 2560 * 2560 + 2 * 2560 * 128 + 62_914_560 + 2 * 2560
    assert (mamba, attention) == (104_161_472, 76_682_240)
    total = 13 * mamba + attention + 65536 * 2560 + 2560
    assert total == 1_598_556_096
    leaves = jax.tree_util.tree_leaves_with_path(built.params)
    count = lambda ls: sum(int(np.prod(x.shape)) for _, x in ls)  # noqa: E731
    assert count(leaves) == total
    by_block = [count([(p, x) for p, x in leaves if p[0].key == f"block_{i}"])
                for i in range(14)]
    assert by_block == [attention if i == 7 else mamba for i in range(14)]
    assert {str(x.dtype) for _, x in leaves} == {"float32"}
    assert "1,598,556,096" in cell.config["reduced_why"]
    # what stays whole on every chip is a hundredth of a percent
    whole = count([(p, x) for p, x in leaves
                   if data_shard_axis(x.shape, 4) is None])
    assert whole == 13 * (5120 * 16 + 5120 * 4 + 3 * 5120 + 192) + 29 * 2560
    # 16 bytes a parameter in the step's state + 4 in the caller's copy, a
    # quarter a chip: 7.4 GiB of 15.75 before an activation
    assert 20 * total / 4 / 2**30 == pytest.approx(7.44, abs=0.01)
    assert 16 * total > V5E.hbm_bytes            # no chip holds it whole


# ------------------------------------------------------------- the readers

def _record(by_group, busy_s=1.0, steps=2, cell=None, chips=4):
    devices = {i: types.SimpleNamespace(by_group=dict(by_group), busy_s=busy_s)
               for i in range(chips)}
    return {"trace": types.SimpleNamespace(devices=devices),
            "trace_steps": steps, "peaks": V5E, "cell": cell or _cell()}


def test_new_readers_on_a_trace_made_by_hand():
    record = _record({
        "pallas:selective_scan_fwd": 0.16, "pallas:selective_scan_bwd": 0.4,
        "pallas:conv_silu_fwd": 0.02, "pallas:conv_silu_bwd": 0.02,
        "pallas:flash_fwd": 0.03, "pallas:flash_bwd_dkv": 0.06,
        "pallas:xent_fwd": 0.06, "pallas:xent_bwd_dw": 0.18,
        "all-gather": 0.2, "all-gather-start": 0.01, "all-reduce": 0.05,
        "collective-permute-done": 0.5, "reduce-scatter": 0.03,
        "fusion (kOutput)": 2.0})
    parts = flops_jamba.parts(record["cell"].config, record["cell"].traffic)
    least = {k: v.least_seconds(V5E) for k, v in parts.items()}
    # every chip's seconds in the denominator: 4 chips x 0.16 s for 2 steps
    assert selective_scan_fwd_roofline_pct.read(record) == \
        pytest.approx(100 * 2 * least["scan_fwd"] / 0.64)
    assert selective_scan_fwd_roofline_pct.read(record) == pytest.approx(14.2, rel=2e-2)
    assert selective_scan_bwd_roofline_pct.read(record) == \
        pytest.approx(100 * 2 * least["scan_bwd"] / 1.6)
    assert jamba_conv_silu_fwd_roofline_pct.read(record) == \
        pytest.approx(100 * 2 * least["conv_fwd"] / 0.08)
    assert jamba_conv_silu_bwd_roofline_pct.read(record) == \
        pytest.approx(100 * 2 * least["conv_bwd"] / 0.08)
    assert jamba_flash_fwd_roofline_pct.read(record) == \
        pytest.approx(100 * 2 * least["flash_fwd"] / 0.12)
    assert jamba_flash_bwd_roofline_pct.read(record) == \
        pytest.approx(100 * 2 * least["flash_bwd"] / 0.24)
    # four logits-sized products of 65,536 x 2,560 x 65,536 a step at the peak
    assert least["xent"] == pytest.approx(4 * 2 * 65536 * 2560 * 65536 / V5E.bf16_flops_per_s)
    assert jamba_xent_roofline_pct.read(record) == \
        pytest.approx(100 * 2 * least["xent"] / 0.96)
    assert selective_scan_time_pct.read(record) == pytest.approx(60.0)
    # a chip, a step: the all-gathers' 0.21 s over 2; the ring steps inside
    # the products, weights' and gradients' alike, are not its
    assert param_gather_ms_per_step.read(record) == pytest.approx(105.0)
    for name in SHARES:
        assert 0 < READERS[name].read(record) <= 100, name
    # a chip's mean seconds in the two traced steps of PR 43's run on the four
    # chips (PERF.md section 5): the scan's shares come out as that run
    # reported them, and the head's, which it did not read yet, under 100
    chip = _record({"pallas:selective_scan_fwd": 0.16310064275,
                    "pallas:selective_scan_bwd": 0.3385663025,
                    "pallas:xent_fwd": 0.06186872775,
                    "pallas:xent_bwd_dw": 0.17507911125})
    assert selective_scan_fwd_roofline_pct.read(chip) == pytest.approx(13.919321359127265)
    assert selective_scan_bwd_roofline_pct.read(chip) == pytest.approx(11.444547057226348)
    assert jamba_xent_roofline_pct.read(chip) == pytest.approx(94.22, abs=0.01)


def test_new_readers_find_nothing_where_there_is_nothing_to_read(monkeypatch):
    # another family's cell, a run without a device trace, a checkout older
    # than the scan's kernels (the parent of this PR): nothing, and no raise
    groups = {"pallas:flash_fwd": 0.3, "pallas:selective_scan_fwd": 0.1,
              "all-gather": 0.1}
    untraced = {"trace": None, "cell": _cell(), "peaks": V5E, "trace_steps": 4}
    for other in ("gpt2m-dp4-sync", "nemotron-pretrain-8k", "kanana-pretrain-16k"):
        record = _record(groups, cell=harness.load_cell(other, ROOT))
        for reader in READERS.values():
            assert reader.read(record) is None
    for reader in READERS.values():
        assert reader.read(untraced) is None
    older = tuple(n for n in kernel_parts.program_kernel_names()
                  if not n.startswith("selective_scan"))
    for names in (None, older):
        monkeypatch.setattr(kernel_parts, "program_kernel_names", lambda: names)
        for reader in READERS.values():
            assert reader.read(_record(groups)) is None


def test_named_kernels_missing_from_the_trace_fail_the_run():
    for name in SHARES:
        with pytest.raises(harness.BenchmarkError, match="no time under"):
            READERS[name].read(_record({"pallas:jvp__": 0.2}))
    # a trace without a collective of the kind reads 0, not nothing
    assert param_gather_ms_per_step.read(_record({"all-reduce": 0.1})) == 0.0


# ------------------------------------------------------ BENCHMARK.json

def test_new_entries_name_files_that_exist_and_cut_what_the_issue_names():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by name, not by place: later PRs append theirs
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert work == {"name": CELL, "config": CONFIG, "traffic": "sharded4-16k",
                    "chips": 4, "why": work["why"]}
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == ("https://huggingface.co/ai21labs/AI21-Jamba2-3B/"
                               "blob/main/config.json")
    assert all(1 <= len(x[k]) <= 200 for x in (entry, work)
               for k in ("why", "source") if k in x)
    cell = _cell()
    for sub in ("families", "reference"):
        cell.find(sub, "jamba.py")
    new = {m["name"]: m for m in bench["per_layer"] if m["name"] in READERS}
    assert set(new) == set(READERS)
    for name, m in new.items():
        assert m["workloads"] == [CELL] and m["source"] == "device_trace"
        assert m["moves"] == "tokens_per_s_per_chip"
        if name.endswith("ms_per_step"):
            assert (m["layer"], m["unit"], m["better"]) == ("sharding", "ms", "lower")
        else:
            assert (m["layer"], m["unit"]) == ("kernels", "%")
            assert m["better"] == ("lower" if name.endswith("time_pct") else "higher")
        assert callable(cell.load_module("layers", name).read)
    # one cell in four may take four chips
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)
    t = cell.traffic
    assert (t["seq_len"], t["micro_batch"], t["accumulation"], t["log_every"],
            t["pool_batches"], t["check_sequences"], t["strategy"], t["mesh"],
            t["chips"]) == (16384, 1, 1, 2, 8, 1, "FullySharded", {"data": 4}, 4)
    # the traffic file's mesh multiplies to its chips
    product = 1
    for size in t["mesh"].values():
        product *= size
    assert product == t["chips"] == work["chips"]
    from autodist_tpu import strategy as strategies
    assert issubclass(getattr(strategies, t["strategy"]),
                      strategies.StrategyBuilder)


def test_the_configuration_keeps_every_published_number_but_the_depth():
    """Against the catalog's own ``config`` where the guide is installed; the
    cut, the deployment and every assumed fact are stated in the file."""
    config = _cell().config
    assert config["num_hidden_layers"] == 14
    assert config["published"] == {"num_hidden_layers": 28}
    assert [r.split()[0] for r in config["reduced"]] == ["num_hidden_layers"]
    widths = dict(hidden_size=2560, intermediate_size=8192, mamba_expand=2,
                  mamba_d_state=16, mamba_dt_rank=160, mamba_d_conv=4,
                  mamba_conv_bias=True, mamba_proj_bias=False,
                  num_attention_heads=20, num_key_value_heads=1,
                  attn_layer_period=14, attn_layer_offset=7, num_experts=1,
                  num_experts_per_tok=1, vocab_size=65536, rms_norm_eps=1e-6,
                  tie_word_embeddings=True, max_position_embeddings=262144,
                  model_type="jamba", hidden_act="silu", sliding_window=None)
    for key, value in widths.items():
        assert config[key] == value, key
    assert config["family"] == "jamba" and config["expects_pallas"] is True
    assert "four chips" in config["deployment"] and "quarters" in config["deployment"]
    assert "second host" in config["deployment"]
    assumed = config["assumed"]
    assert (assumed["ssm_impl"], assumed["attention_impl"], assumed["fused_head"],
            assumed["remat"], assumed["scan_chunk"], assumed["optimizer"]) == \
        ("pallas", "flash", True, True, 128, "adamw")
    assert "layers_block_type" in assumed["layer_order"]
    family = _cell().load_module("families", "jamba")
    for key, computed in family.COMPUTED:
        with pytest.raises(ValueError, match=key):
            family.model_config(dict(config, **{key: "other"}))
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "AI21-Jamba2-3B")
    assert row["source_url"] in config["source"]
    for key, value in row["config"].items():
        if key != "num_hidden_layers":
            assert config[key] == value, key
        else:
            assert config["published"][key] == value


# ----------------------------------------------------------- CPU rehearsal

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The shared scratch root plus a tiny Jamba configuration and a cell on
    four devices under ``FullySharded``, as new files and entries: three
    layers (M*M), one KV head under four query heads."""
    root = scratch.make_root(tmp_path_factory.mktemp("jamba_root"))
    with open(os.path.join(ROOT, "benchmark", "configs", f"{CONFIG}.json")) as f:
        config = json.load(f)
    config.update(hidden_size=256, intermediate_size=512, mamba_d_state=8,
                  mamba_dt_rank=16, num_attention_heads=4,
                  num_hidden_layers=3, attn_layer_period=3, attn_layer_offset=1,
                  vocab_size=1024, max_position_embeddings=64)
    config["assumed"] = dict(
        config["assumed"], learning_rate=0.003, scan_chunk=16,
        ssm_impl="xla")          # the kernels want 1,024 channels
    with open(os.path.join(root, "extra", "configs", "tiny-jamba.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(ROOT, "benchmark", "traffic", "sharded4-16k.json")) as f:
        traffic = json.load(f)
    traffic.update(seq_len=48, micro_batch=1, log_every=2, check_sequences=1)
    with open(os.path.join(root, "extra", "traffic", "tiny-sharded4.json"), "w") as f:
        json.dump(traffic, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-jamba", "source": "test only",
                             "reduced": [], "why": "test only",
                             "file": "extra/configs/tiny-jamba.json"})
    bench["workloads"].append({"name": "tiny-jamba-sharded4",
                               "config": "tiny-jamba", "traffic": "tiny-sharded4",
                               "chips": 4, "why": "test only"})
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_tiny_jamba_cell_end_to_end_on_four_virtual_devices(root, trace):
    """A seed past 2**31, as the driver's are. The table (1,024 x 256) and
    every matrix are stored as quarters; the check's jit has no mesh in scope
    and the family's loss brings its own."""
    line = test_harness_cpu._rehearse(root, "tiny-jamba-sharded4", devices=4,
                                      trace=trace, seed=3000000019, seconds=4.0)
    test_harness_cpu._check_shape(line, 4)
    reference = line["checks"]["reference"]
    assert reference["loss_rel_diff"] < 2e-3 and reference["grad_rel_l2"] < 3e-2
    compiled = line["checks"]["compiled"]
    assert compiled["param_device_set_sizes"] == [4]
    assert "all-gather" in compiled["collectives"]
    if trace:
        # no device trace on the CPU: the new readers give nothing
        assert not set(line["metrics"]) & set(READERS)
        assert "compiled_hbm_gib" in line["metrics"]
    else:
        assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
