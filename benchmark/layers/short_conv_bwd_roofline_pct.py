"""Kernels: the least time the chip could take for the gated short
convolution's backward kernel in the traced steps (``benchmark/flops_lfm2.py``
``short_conv_cost``: ``bcu`` and ``dy`` read, ``dbcu`` written, 14 bytes an
element of ``[tokens, d]``, each once, at the chip's memory bandwidth; the
taps' gradient is nothing beside them), over the self seconds the trace holds
under ``pallas:short_conv_bwd``, all chips. Nothing to read for another
family's configuration or a program that does not name the kernel."""

from benchmark import flops_lfm2


def read(record):
    return flops_lfm2.roofline_pct(record, "conv_bwd", flops_lfm2.CONV_BWD)
