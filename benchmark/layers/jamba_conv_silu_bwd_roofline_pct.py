"""Kernels: as ``jamba_conv_silu_fwd_roofline_pct`` for the backward kernel
(``pallas:conv_silu_bwd``): ``x`` and ``dy`` read and ``dx`` written, once
each at two bytes an element, at the memory bandwidth, over the kernel's self
seconds, all chips."""

from benchmark import flops_jamba


def read(record):
    return flops_jamba.roofline_pct(record, "conv_bwd", flops_jamba.CONV_BWD)
