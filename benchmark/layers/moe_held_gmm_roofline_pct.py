"""Kernels: the least time the chip could take for the grouped matmuls of
the experts held here in the traced steps (``benchmark/flops_moe.py``
``gmm_cost`` at the rows the held experts receive on average, tokens x top_k
x held / router width, and the held banks: nine products, each product's
operands and result moved once), over the self seconds the trace holds under
``pallas:moe_gmm_fwd`` + ``pallas:moe_gmm_bwd_dx`` + ``pallas:moe_gmm_bwd_dw``,
all chips. The kernels walk the static ``rows_bound`` prefix, of which the
held rows are a part, so the share also pays for the prefix's empty tail.
Nothing to read for another family's configuration or a program that names
no kernel."""

from benchmark import flops_afmoe, flops_moe


def read(record):
    return flops_afmoe.roofline_pct(record, "gmm", flops_moe.GMM_KERNELS)
