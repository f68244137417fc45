"""Kernels: the least time the chip could take for the grouped matmuls of
the gated-SiLU experts held here in the traced steps
(``benchmark/flops_moe.py`` ``gmm_cost`` at the rows the held experts receive
on average, tokens x top_k x held / router width = 6,144, the three held banks
of 2,048 x 768: nine products, each product's operands and result moved
once), over the self seconds the trace holds under ``pallas:moe_gmm_fwd`` +
``pallas:moe_gmm_bwd_dx`` + ``pallas:moe_gmm_bwd_dw``, all chips. The kernels
walk the ``rows_bound`` rows of a pass, of which the held rows are a part, so
the share pays for the tail. Nothing to read for another family's
configuration."""

from benchmark import flops_deepseek_v3, flops_moe


def read(record):
    return flops_deepseek_v3.roofline_pct(record, "gmm", flops_moe.GMM_KERNELS)
