"""Kernels: the least time the chip could take for the forward kernel of the
depthwise convolution with bias and SiLU before the selective scan in the
traced steps (``benchmark/flops_jamba.py`` ``conv_cost``: no product, ``[T,
5,120]`` read and written once at two bytes an element, summed over the
configuration's Mamba-1 layers, once a step), over the self seconds the trace
holds under ``pallas:conv_silu_fwd``, all chips. Under per-layer recomputation
the kernel runs twice a step (and once more, on float32 operands, for the
first layer's precise value), so the share reads at most half of what a call
reaches. Nothing to read for another family's configuration."""

from benchmark import flops_jamba


def read(record):
    return flops_jamba.roofline_pct(record, "conv_fwd", flops_jamba.CONV_FWD)
