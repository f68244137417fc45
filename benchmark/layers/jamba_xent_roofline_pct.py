"""Kernels: the least time the chip could take for the fused head + loss over
the tied table in the traced steps (``benchmark/flops_jamba.py`` ``parts``
``"xent"``: ``flops.fused_xent_cost``'s four logits-sized products of
65,536 tokens x 2,560 x 65,536 a step, which is what the forward kernel and
the one-pass backward run), over the self seconds the trace holds under
``pallas:xent_fwd`` / ``xent_bwd_dh`` / ``xent_bwd_dw``, all chips. The head
is a tenth of the model's operations and 6% of the step. Nothing to read for
another family's configuration."""

from benchmark import flops_jamba, kernel_parts


def read(record):
    return flops_jamba.roofline_pct(record, "xent", kernel_parts.XENT)
