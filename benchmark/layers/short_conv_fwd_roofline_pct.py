"""Kernels: the least time the chip could take for the gated short
convolution's forward kernel in the traced steps, over the self seconds the
trace holds under ``pallas:short_conv_fwd``, all chips. The least time is
``benchmark/flops_lfm2.py`` ``short_conv_cost``: no matrix product, so the
bytes of the three thirds of ``bcu`` read and ``y`` written (8 bytes an
element of ``[tokens, d]``), each once, at the chip's memory bandwidth, summed
over the configuration's conv layers. Nothing to read for another family's
configuration or a program that does not name the kernel."""

from benchmark import flops_lfm2


def read(record):
    return flops_lfm2.roofline_pct(record, "conv_fwd", flops_lfm2.CONV_FWD)
