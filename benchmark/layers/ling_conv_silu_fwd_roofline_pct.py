"""Kernels: the least time the chip could take for the forward kernel of the
three depthwise 4-tap convolutions with SiLU before the recurrence in the
traced steps (``benchmark/flops_bailing_hybrid.py`` ``conv_cost``: no product,
``[T, 2,048]`` read and written once at two bytes an element, three times a
KDA layer, summed over the configuration's KDA layers, once a step), over the
self seconds the trace holds under ``pallas:conv_silu_fwd``, all chips. Under
per-layer recomputation the kernel runs twice a step, so the share reads under
half of what a call reaches. Nothing to read for another family's
configuration or a program that does not name the kernel."""

from benchmark import flops_bailing_hybrid


def read(record):
    return flops_bailing_hybrid.roofline_pct(record, "conv_fwd",
                                             flops_bailing_hybrid.CONV_FWD)
