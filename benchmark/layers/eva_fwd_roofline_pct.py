"""Kernels: the least time the chip could take for EVA attention's forward
kernel (``eva_fwd``: a q block's walk over the earlier windows' summaries and
then its own window's causal tiles, one online softmax) in the traced steps,
over the self seconds the trace holds under ``pallas:eva_fwd``, all chips.
The least time is ``benchmark/flops_evabyte.py`` ``eva_cost``: the two
forward products over the visible (query, key-or-summary) pairs, ``pairs x
(128 + 128)`` multiply-adds a head, at the chip's bf16 peak, or q, k, v, o and
the two summary arrays moved once at the memory bandwidth, whichever is
larger. The kernels' tiles hold more pairs than the mask keeps
(``eva_tile_fill_pct``), so this share cannot reach what a call's tiles
reach. Nothing to read for another family's configuration or a program that
does not name the kernel."""

from benchmark import flops_evabyte


def read(record):
    return flops_evabyte.roofline_pct(record, "eva_fwd", flops_evabyte.EVA_FWD)
