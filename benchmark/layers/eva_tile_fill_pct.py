"""Kernels: the (query, key-or-summary) pairs EVA's mask keeps, as
``benchmark/flops_evabyte.py`` ``visible_pairs`` requires them of every layer
and head held of a step, as a share of the pairs in the score tiles the
kernels run, in percent, from the program's own trace-time count (gauge
``eva.pairs.computed``: ``ops/eva_attention.py`` ``eva_pairs``). The
summaries' tiles are whole (a window's 128 summaries against a q block, never
masked); the window's causal tiles are ``[512, 512]`` and the diagonal's are
half full: 24,125,440 of 28,311,552 a head at 16,384 positions, 85.2. A kernel
that computed masked summary tiles or whole causal squares would show here.
Nothing to read for another family's configuration or a program without the
gauge."""

import math

from benchmark import flops_evabyte, program_counters


def read(record):
    if not flops_evabyte.is_cell(record):
        return None
    computed = program_counters.value("eva.pairs.computed")
    if not computed:
        return None
    cell = record["cell"]
    s = flops_evabyte.shape(cell.config)
    sequences = cell.traffic["micro_batch"] * math.prod(
        cell.traffic["mesh"].values())
    visible = sum(flops_evabyte.visible_pairs(
        cell.traffic["seq_len"], s["window"], s["chunk"]))
    return 100.0 * visible * sequences * s["heads"] * s["n_layers"] / computed
