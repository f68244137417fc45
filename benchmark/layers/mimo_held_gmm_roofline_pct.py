"""Kernels: the least time the chip could take for the grouped matmuls of
the gated-SiLU experts held here in the traced steps
(``benchmark/flops_moe.py`` ``gmm_cost`` at the rows a chip's held experts
receive on average, 8,192 tokens x top 8 x 8 held / 256 = 2,048, against the
three banks of 8 x 4,096 x 2,048 each chip reads from its own gathered copy:
nine products, each product's operands and result moved once, times the
chips), over the self seconds the trace holds under ``pallas:moe_gmm_fwd`` +
``pallas:moe_gmm_bwd_dx`` + ``pallas:moe_gmm_bwd_dw``, all chips. At 256 rows
an expert the banks' bytes, not the products, are the least time; the kernels
walk the ``rows_bound`` rows of a pass, of which the held rows are half.
Nothing to read for another family's configuration."""

from benchmark import flops_mimo_v2, flops_moe


def read(record):
    return flops_mimo_v2.roofline_pct(record, "gmm", flops_moe.GMM_KERNELS)
