"""Kernels: the least time the chip could take for the backward kernel of the
depthwise convolution with bias and SiLU before the scan in the traced steps
(``benchmark/flops_nemotron_h.py`` ``conv_cost``: ``x`` and ``dy`` read, ``dx``
written, each once at two bytes an element; the taps' and the bias's gradients
are sums that stay on the chip), over the self seconds the trace holds under
``pallas:conv_silu_bwd``, all chips. Nothing to read for another family's
configuration or a program that does not name the kernel."""

from benchmark import flops_nemotron_h


def read(record):
    return flops_nemotron_h.roofline_pct(record, "conv_bwd",
                                         flops_nemotron_h.CONV_BWD)
