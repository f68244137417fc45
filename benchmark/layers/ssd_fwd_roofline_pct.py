"""Kernels: the least time the chip could take for the chunked state-space
scan's forward kernel in the traced steps, over the self seconds the trace
holds under ``pallas:ssd_fwd``, all chips. The least time is
``benchmark/flops_nemotron_h.py`` ``ssd_cost``: the larger of the scan's
products at the chip's bf16 peak (a group's ``C B^T`` over the causal half of a
chunk, per head the masked plane against ``dt x``, the entering state against
``C`` and the chunk's addition to it) and of its bytes at the memory bandwidth
(``x``, ``B``, ``C`` read, ``y`` and one float32 ``[P, N]`` state a chunk and
head written, each once), summed over the configuration's Mamba-2 layers, once
a step: under per-layer recomputation the kernel runs twice a step, so the
share reads at most half of what a call reaches. Nothing to read for another
family's configuration or a program that does not name the kernel."""

from benchmark import flops_nemotron_h


def read(record):
    return flops_nemotron_h.roofline_pct(record, "ssd_fwd",
                                         flops_nemotron_h.SSD_FWD)
