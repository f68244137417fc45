"""Kernels: the least time the chip could take for the grouped matmuls of
the ``relu2`` experts held here in the traced steps
(``benchmark/flops_nemotron_h.py`` ``relu2_gmm_cost`` at the rows the held
experts receive on average, tokens x top_k x held / router width = 3,072, the
two held banks of 2,688 x 1,856: two products forward and four backward, each
product's operands and result moved once), over the self seconds the trace
holds under ``pallas:moe_gmm_fwd`` + ``pallas:moe_gmm_bwd_dx`` +
``pallas:moe_gmm_bwd_dw``, all chips. The kernels walk the ``rows_bound`` rows
of a pass, of which the held rows are a part, and under per-layer
recomputation the forward's two run twice, so the share pays for both. Nothing
to read for another family's configuration."""

from benchmark import flops_moe, flops_nemotron_h


def read(record):
    return flops_nemotron_h.roofline_pct(record, "gmm", flops_moe.GMM_KERNELS)
