"""Kernels: the least time the chip could take for EVA attention's backward
kernel (``eva_bwd``: one pass a window, dQ, dK, dV and the summaries' float32
gradients from one recomputed score tile) in the traced steps, over the self
seconds the trace holds under ``pallas:eva_bwd``, all chips. The least time
is ``benchmark/flops_evabyte.py`` ``eva_cost``: five products over the
visible pairs (the scores again, dP, dV, dQ, dK) at the chip's bf16 peak, or
q, k, v, o, dO and the summaries read and dq, dk, dv and the summaries'
gradients written once at the memory bandwidth, whichever is larger. Nothing
to read for another family's configuration or a program that does not name
the kernel."""

from benchmark import flops_evabyte


def read(record):
    return flops_evabyte.roofline_pct(record, "eva_bwd", flops_evabyte.EVA_BWD)
