"""Kernels: the least time the chip could take for the routed FFN's grouped
matmuls in the traced steps (``benchmark/flops_moe.py`` ``gmm_cost``: nine
products of rows x d_model x d_expert, each product's operands and result
moved once; operations over peak FLOP/s or bytes over peak bytes/s, whichever
is larger), over the self seconds the trace holds under ``pallas:moe_gmm_fwd``
+ ``pallas:moe_gmm_bwd_dx`` + ``pallas:moe_gmm_bwd_dw``, all chips. Nothing
to read in a program that does not name these kernels; fails the run where
the program names them and the trace holds none."""

from benchmark import flops_moe


def read(record):
    steps, peaks = record.get("trace_steps"), record.get("peaks")
    if not steps or peaks is None:
        return None
    measured = flops_moe.gmm_seconds(record)
    if measured is None:
        return None
    least = flops_moe.cell_gmm_cost(record["cell"]).least_seconds(peaks)
    return 100.0 * least * steps / measured
