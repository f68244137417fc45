"""Kernels: the least time the chip could take for the fused head + loss
over the untied 4,096 x 19,072 head in the traced steps
(``benchmark/flops_mimo_v2.py`` ``parts`` ``"xent"``: ``flops.fused_xent_cost``'s
four logits-sized products of a chip's 8,192 tokens, its own gathered copy of
the table read in both passes, times the chips), over the self seconds the
trace holds under ``pallas:xent_fwd`` / ``xent_bwd_dh`` / ``xent_bwd_dw``, all
chips. Nothing to read for another family's configuration."""

from benchmark import flops_mimo_v2, kernel_parts


def read(record):
    return flops_mimo_v2.roofline_pct(record, "xent", kernel_parts.XENT)
