"""Kernels: the least time the chip could take for the forward kernel of Kimi
Delta Attention's chunked recurrence in the traced steps, over the self
seconds the trace holds under ``pallas:kda_fwd``, all chips. The least time is
``benchmark/flops_bailing_hybrid.py`` ``kda_cost``: the larger of the
recurrence's products at the chip's bf16 peak (per chunk and head the two
triangles over the causal half of the chunk's pairs, the triangular solve as a
substitution needs it and not as the kernel's doubling executes it, the
triangle against the corrected values, three products with the ``[128, 128]``
state) and of its bytes at the memory bandwidth (``q``, ``k``, ``v`` and the
float32 log-decay read, ``o`` and one float32 state a chunk and head written,
each once), summed over the configuration's KDA layers, once a step. Nothing to
read for another family's configuration or a program that does not name the
kernel."""

from benchmark import flops_bailing_hybrid


def read(record):
    return flops_bailing_hybrid.roofline_pct(record, "kda_fwd",
                                             flops_bailing_hybrid.KDA_FWD)
