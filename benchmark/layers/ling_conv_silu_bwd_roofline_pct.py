"""Kernels: as ``ling_conv_silu_fwd_roofline_pct``, for the convolutions'
backward kernel (``pallas:conv_silu_bwd``: ``x`` and ``dy`` read and ``dx``
written, ``[T, 2,048]`` each at two bytes an element, three times a KDA
layer). Nothing to read for another family's configuration or a program that
does not name the kernel."""

from benchmark import flops_bailing_hybrid


def read(record):
    return flops_bailing_hybrid.roofline_pct(record, "conv_bwd",
                                             flops_bailing_hybrid.CONV_BWD)
