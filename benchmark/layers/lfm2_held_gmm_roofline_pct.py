"""Kernels: the least time the chip could take for the grouped matmuls of
the experts held here in the traced steps (``benchmark/flops_moe.py``
``gmm_cost`` at the rows the held experts receive on average, tokens x top_k
x held / router width, the held banks and this family's expert width 1,536:
nine products, each product's operands and result moved once), over the self
seconds the trace holds under ``pallas:moe_gmm_fwd`` + ``pallas:moe_gmm_bwd_dx``
+ ``pallas:moe_gmm_bwd_dw``, all chips. The kernels walk the ``rows_bound``
rows of a pass, of which the held rows are a part, so the share also pays for
a pass's empty tail. Nothing to read for another family's configuration."""

from benchmark import flops_lfm2, flops_moe


def read(record):
    return flops_lfm2.roofline_pct(record, "gmm", flops_moe.GMM_KERNELS)
