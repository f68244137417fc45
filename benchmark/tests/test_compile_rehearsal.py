"""Every cell's step compiled at its real size for a described ``v5e:2x2``
(``benchmark/rehearse.py``), before any chip time: the chip's compiler
accepts it, it fits a 16 GB chip beside the parameters ``train()`` is
handed, the GPT-2 cells hold Mosaic kernels and the BERT cells none, and
the four-chip cell holds a gradient all-reduce. About a minute a cell.
Nothing runs: a pass here is not a chip run. All in this one file, with the
topology in a fixture: only one process at a time can load the TPU's
library."""

import json

import pytest

from benchmark import harness, rehearse

CHIP_GIB = 15.75      # what a v5e's runtime leaves of its 16 GiB
CELLS = ["gpt2m-pretrain-1k", "gpt2m-dp4-sync", "bertl-pretrain-128",
         "bertl-replica-b32"]


@pytest.fixture(scope="module")
def topology():
    try:
        return rehearse.describe_topology()
    except Exception as e:  # noqa: BLE001 — any failure to describe is a skip
        pytest.skip(f"cannot describe a {rehearse.TOPOLOGY} topology here: {e}")


@pytest.mark.parametrize("name", CELLS)
def test_cell_compiles_for_the_described_chip(topology, name, record_property):
    cell = harness.load_cell(name)
    with rehearse.steer_kernels_to_compile():
        facts = rehearse.compile_cell(cell, topology.devices)
    record_property("memory_analysis", json.dumps(facts))
    print(json.dumps(facts))
    params_gib = facts["parameters"] * 4 / 2**30     # the copy train() holds
    assert facts["step_gib"] + params_gib < CHIP_GIB
    assert facts["step_gib"] > 0.25 * 16             # the contract's floor
    assert facts["tpu_custom_call"] == bool(cell.config["expects_pallas"])
    if cell.chips > 1:
        assert "all-reduce" in facts["collectives"]
    else:
        assert facts["collectives"] == []
