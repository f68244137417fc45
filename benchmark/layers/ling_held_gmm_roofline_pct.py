"""Kernels: the least time the chip could take for the grouped matmuls of
the gated-SiLU experts held here in the traced steps
(``benchmark/flops_moe.py`` ``gmm_cost`` at the rows the held experts receive
on average, tokens x top_k x held / router width = 1,024, the three held banks
of 2,560 x 768: nine products, each product's operands and result moved
once), over the self seconds the trace holds under ``pallas:moe_gmm_fwd`` +
``pallas:moe_gmm_bwd_dx`` + ``pallas:moe_gmm_bwd_dw``, all chips. The kernels
walk the ``rows_bound`` rows of a pass, of which the held rows are a part, so
the share pays for the tail: at 1/64 of a rank's load the banks' bytes, not
the rows' products, are most of the least time. Nothing to read for another
family's configuration."""

from benchmark import flops_bailing_hybrid, flops_moe


def read(record):
    return flops_bailing_hybrid.roofline_pct(record, "gmm",
                                             flops_moe.GMM_KERNELS)
