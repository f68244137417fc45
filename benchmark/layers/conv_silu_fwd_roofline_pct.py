"""Kernels: the least time the chip could take for the forward kernel of the
depthwise convolution with bias and SiLU before the scan in the traced steps
(``benchmark/flops_nemotron_h.py`` ``conv_cost``: no product, ``[T, 6,144]``
read and written once at two bytes an element, summed over the configuration's
Mamba-2 layers, once a step), over the self seconds the trace holds under
``pallas:conv_silu_fwd``, all chips. Under per-layer recomputation the kernel
runs twice a step, and layer 0 moves float32, so the share reads under half of
what a call reaches. Nothing to read for another family's configuration or a
program that does not name the kernel."""

from benchmark import flops_nemotron_h


def read(record):
    return flops_nemotron_h.roofline_pct(record, "conv_fwd",
                                         flops_nemotron_h.CONV_FWD)
