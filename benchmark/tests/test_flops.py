"""The required-operations functions against counts made by hand."""

import json
import os

import pytest

from benchmark import flops, peaks
from benchmark.tests.conftest import ROOT


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_gpt2_medium_train_flops_per_token_by_hand():
    c = _config("gpt2-medium")
    assert (c["n_embd"], c["n_layer"], c["n_inner"], c["vocab_size"]) == \
        (1024, 24, 4096, 50257)
    # one block, forward, a position: q k v out 4 x 1024^2 multiply-adds,
    # MLP 2 x 1024 x 4096, causal scores + values 2 x (1024/2) x 1024
    block = 2 * (4 * 1024**2 + 2 * 1024 * 4096 + 1024 * 1024)
    assert block == 27_262_976
    head = 2 * 1024 * 50257
    by_hand = 3 * (24 * block + head)
    assert by_hand == 2_271_713_280
    got = flops.train_flops_per_token(
        d_model=1024, n_layers=24, d_ff=4096, vocab_size=50257, seq_len=1024,
        causal=True)
    assert got == by_hand


def test_bert_large_train_flops_per_token_by_hand():
    c = _config("bert-large")
    assert (c["hidden_size"], c["num_hidden_layers"], c["intermediate_size"],
            c["vocab_size"]) == (1024, 24, 4096, 30522)
    # bidirectional at 128 positions: scores + values 2 x 128 x 1024
    # multiply-adds; the head on 20 of 128 positions
    block = 2 * (4 * 1024**2 + 2 * 1024 * 4096 + 2 * 128 * 1024)
    assert block == 25_690_112
    head = 2 * 1024 * 30522 * 20 / 128
    by_hand = 3 * (24 * block + head)
    assert by_hand == 1_878_989_184
    got = flops.train_flops_per_token(
        d_model=1024, n_layers=24, d_ff=4096, vocab_size=30522, seq_len=128,
        causal=False, predicted_fraction=20 / 128)
    assert got == by_hand


def test_causal_mask_halves_attention_only():
    full = flops.layer_forward_flops_per_token(1024, 4096, 1024, causal=False)
    half = flops.layer_forward_flops_per_token(1024, 4096, 1024, causal=True)
    assert full - half == 2 * 1024 * 1024     # 2.1M of 27M: what utils/flops.py overcounts


def test_kernel_costs_by_hand():
    v5e = peaks.peaks_for("TPU v5 lite")
    flash = flops.flash_attention_cost(batch=8, seq_len=1024, n_heads=16,
                                       head_dim=64, causal=True)
    # 7 products of 8 x 16 x 1024 x 1024 x 64 multiply-adds, halved
    assert flash.flops == 7 * 2 * 8 * 16 * 1024 * 1024 * 64 / 2
    assert flash.hbm_bytes == 12 * 8 * 1024 * 16 * 64 * 2
    assert flash.bound(v5e) == "compute"
    xent = flops.fused_xent_cost(rows=8192, d_model=1024, vocab_size=50257)
    assert xent.flops == 4 * 2 * 8192 * 1024 * 50257
    assert xent.hbm_bytes == 3 * 8192 * 1024 * 2 + 3 * 50257 * 1024 * 4
    assert xent.bound(v5e) == "compute"
    both = flash * 24 + xent
    assert both.least_seconds(v5e) == pytest.approx(both.flops / 197e12)


def test_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")
