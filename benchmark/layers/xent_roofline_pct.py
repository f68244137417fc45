"""Kernels: the least time the chip could take for the fused head + loss
(``xent_fwd`` + ``xent_bwd_dh`` + ``xent_bwd_dw``) in the traced steps, four
logits-sized products (the kernels run five: each backward kernel recomputes
the logits) (``benchmark/kernel_parts.py``), over the self seconds the trace
holds under the kernels' own names, all chips. Fails the run where the
program names its kernels and the trace does not."""

from benchmark import kernel_parts


def read(record):
    return kernel_parts.roofline_pct(record, "xent", kernel_parts.XENT)
