"""What PR 25 added, by new files only: the routed model's required
operations against counts made by hand, the grouped-matmul readers on a trace
made by hand, ``BENCHMARK.json``'s new entries, and a tiny ``olmoe`` cell
end to end on the CPU through ``run_cell(require_tpu=False)``."""

import json
import os
import types

import pytest

from benchmark import flops_moe, harness, kernel_parts, peaks
from benchmark.layers import moe_gmm_roofline_pct, moe_gmm_time_pct
from benchmark.tests import scratch, test_harness_cpu
from benchmark.tests.conftest import ROOT

V5E = peaks.peaks_for("TPU v5 lite")
CELL = "olmoe-pretrain-4k"


def _cell():
    return harness.load_cell(CELL, ROOT)


# ------------------------------------------------------------ required work

def test_olmoe_train_flops_per_token_by_hand():
    c = _cell().config
    assert (c["hidden_size"], c["intermediate_size"], c["num_experts"],
            c["num_experts_per_tok"], c["vocab_size"], c["num_hidden_layers"]) \
        == (2048, 1024, 64, 8, 50304, 1)
    projections = 2 * 4 * 2048 * 2048              # q, k, v, out
    attention = 2 * 4096 * 2048                    # causal: half of 4 x s x d
    router = 2 * 2048 * 64
    experts = 8 * 3 * 2 * 2048 * 1024              # 8 of 64, gate + up + down
    head = 2 * 2048 * 50304
    assert (projections, attention, router, experts, head) == \
        (33_554_432, 16_777_216, 262_144, 100_663_296, 206_045_184)
    by_hand = 3 * (projections + attention + router + experts + head)
    assert by_hand == 1_071_906_816
    got = flops_moe.train_flops_per_token(
        d_model=2048, n_layers=1, d_expert=1024, n_experts=64, top_k=8,
        vocab_size=50304, seq_len=4096)
    assert got == by_hand
    # the shares the cell's `why` quotes
    assert round(100 * 3 * experts / by_hand) == 28
    assert round(100 * 3 * head / by_hand) == 58


def test_gmm_cost_by_hand_and_the_family_counts_it():
    """131,072 rows: one product is 2 x 131072 x 2048 x 1024 operations, nine
    of them a step. Bytes: a [rows, 2048] and a [rows, 1024] bfloat16 tensor
    and a bank of 64 x 2048 x 1024 (bfloat16 as an operand, float32 as dW's
    result) a product."""
    cell = _cell()
    cost = flops_moe.cell_gmm_cost(cell)
    product = 2 * 131072 * 2048 * 1024
    assert cost.flops == 9 * product == 4_947_802_324_992
    wide, narrow, bank = 131072 * 2048 * 2, 131072 * 1024 * 2, 64 * 2048 * 1024
    assert cost.hbm_bytes == 9 * (wide + narrow) + 6 * bank * 2 + 3 * bank * 4
    assert cost.bound(V5E) == "compute"
    assert cost.least_seconds(V5E) == pytest.approx(0.025116, rel=1e-3)
    family = cell.load_module("families", "olmoe")
    built = family.build(cell.config, dict(cell.traffic, pool_batches=1), 0, 4,
                         abstract=True)
    assert built.train_flops_per_token == 1_071_906_816
    assert built.tokens_per_step == 16384
    from benchmark import flops
    others = flops.flash_attention_cost(batch=4, seq_len=4096, n_heads=16,
                                        head_dim=128, causal=True) \
        + flops.fused_xent_cost(rows=16384, d_model=2048, vocab_size=50304)
    assert built.kernel_cost_per_step.flops == \
        pytest.approx(cost.flops + others.flops)
    assert flops_moe.cell_gmm_cost(harness.load_cell("gpt2m-pretrain-1k")) is None


# ------------------------------------------------------------- the readers

def _record(by_group, busy_s=1.0, steps=4, cell=None):
    device = types.SimpleNamespace(by_group=by_group, busy_s=busy_s)
    trace = types.SimpleNamespace(devices={0: device})
    return {"trace": trace, "trace_steps": steps, "peaks": V5E,
            "cell": cell or _cell()}


def test_gmm_readers_on_a_trace_made_by_hand():
    record = _record({"pallas:moe_gmm_fwd": 0.05, "pallas:moe_gmm_bwd_dx": 0.05,
                      "pallas:moe_gmm_bwd_dw": 0.1, "pallas:xent_fwd": 0.3,
                      "fusion (kOutput)": 0.4})
    # 4 steps need 4 x 25.116 ms at the roofline and took 200 ms
    assert moe_gmm_roofline_pct.read(record) == pytest.approx(50.23, rel=1e-3)
    assert moe_gmm_time_pct.read(record) == pytest.approx(20.0)


def test_gmm_readers_find_nothing_in_a_program_without_the_kernels(monkeypatch):
    record = _record({"pallas:xent_fwd": 0.3})
    # a checkout older than the names, or than these kernels: nothing, no raise
    monkeypatch.setattr(kernel_parts, "program_kernel_names", lambda: None)
    assert moe_gmm_roofline_pct.read(record) is None
    assert moe_gmm_time_pct.read(record) is None
    monkeypatch.setattr(kernel_parts, "program_kernel_names",
                        lambda: ("flash_fwd", "xent_fwd"))
    assert moe_gmm_roofline_pct.read(record) is None
    # no trace (the CPU rehearsal), a configuration without experts
    assert moe_gmm_time_pct.read({"trace": None, "cell": _cell()}) is None
    monkeypatch.undo()
    dense = _record({}, cell=harness.load_cell("gpt2m-pretrain-1k"))
    assert moe_gmm_roofline_pct.read(dense) is None


def test_named_kernels_missing_from_the_trace_fail_the_run():
    with pytest.raises(harness.BenchmarkError, match="moe_gmm_fwd"):
        moe_gmm_roofline_pct.read(_record({"pallas:jvp__": 0.2}))


# ------------------------------------------------------ BENCHMARK.json

def test_new_entries_name_files_that_exist_and_cut_only_the_depth():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert [w["name"] for w in bench["workloads"]][-2:] == \
        ["bertl-pretrain-512", CELL]
    entry = bench["configs"][-1]
    assert entry["name"] == "olmoe-1b-7b" and entry["reduced"] == ["num_hidden_layers"]
    assert all(len(x[k]) <= 200 for x in bench["configs"] + bench["workloads"]
               for k in ("why", "source") if k in x)
    cell = _cell()
    assert cell.config["reduced"] == ["num_hidden_layers 16 -> 1"]
    assert cell.config["departures"] and cell.config["deployment"]
    for sub in ("families", "reference"):
        cell.find(sub, "olmoe.py")
    new = [m for m in bench["per_layer"] if m["name"].startswith("moe_")]
    assert [m["name"] for m in new] == ["moe_gmm_roofline_pct", "moe_gmm_time_pct"]
    assert [m["name"] for m in new] == [m["name"] for m in bench["per_layer"]][-len(new):]
    for m in new:
        assert m["workloads"] == [CELL] and m["layer"] == "kernels"
        assert callable(cell.load_module("layers", m["name"]).read)
    bert = harness.load_cell("bertl-pretrain-512", ROOT)
    t = bert.traffic
    assert t["micro_batch"] * t["accumulation"] == 256 and t["seq_len"] == 512
    assert t["predictions"] == 76 and bert.config["family"] == "bert"


# ----------------------------------------------------------- CPU rehearsal

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The shared scratch root plus a tiny OLMoE configuration and cell, as
    new files and entries."""
    root = scratch.make_root(tmp_path_factory.mktemp("olmoe_root"))
    with open(os.path.join(ROOT, "benchmark", "configs", "olmoe-1b-7b.json")) as f:
        config = json.load(f)
    config.update(hidden_size=64, intermediate_size=32, num_attention_heads=4,
                  num_key_value_heads=4, num_hidden_layers=2, num_experts=8,
                  num_experts_per_tok=2, vocab_size=503,
                  max_position_embeddings=64)
    with open(os.path.join(root, "extra", "configs", "tiny-olmoe.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(ROOT, "benchmark", "traffic", "pretrain-4k.json")) as f:
        traffic = json.load(f)
    traffic.update(seq_len=32, micro_batch=2, log_every=2, check_sequences=2)
    with open(os.path.join(root, "extra", "traffic", "tiny-4k.json"), "w") as f:
        json.dump(traffic, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-olmoe", "source": "test only",
                             "reduced": [], "why": "test only",
                             "file": "extra/configs/tiny-olmoe.json"})
    bench["workloads"].append({"name": "tiny-olmoe-4k", "config": "tiny-olmoe",
                               "traffic": "tiny-4k", "chips": 1,
                               "why": "test only"})
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_tiny_olmoe_cell_end_to_end_on_the_cpu(root, trace):
    line = test_harness_cpu._rehearse(root, "tiny-olmoe-4k", devices=1,
                                      trace=trace, seconds=4.0)
    test_harness_cpu._check_shape(line, 1)
    reference = line["checks"]["reference"]
    assert reference["loss_rel_diff"] < 1e-3 and reference["grad_rel_l2"] < 3e-2
    if trace:
        # no device trace on the CPU: the moe readers give nothing
        assert not {n for n in line["metrics"] if n.startswith("moe_")}
        assert "compiled_hbm_gib" in line["metrics"]
    else:
        assert set(line["metrics"]) == {"tokens_per_s_per_chip", "setup_s"}
