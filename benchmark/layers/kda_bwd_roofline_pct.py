"""Kernels: as ``kda_fwd_roofline_pct``, for the recurrence's backward kernel
(``pallas:kda_bwd``): twice the forward's products (each transposed twice; the
two triangles, the solve and the corrected values the kernel builds again are
recomputation and not counted) and ``q``, ``k``, ``v``, ``dO``, the float32
log-decay and the chunks' states read, ``dq``, ``dk``, ``dv`` and the float32
``dg`` written. Nothing to read for another family's configuration or a
program that does not name the kernel."""

from benchmark import flops_bailing_hybrid


def read(record):
    return flops_bailing_hybrid.roofline_pct(record, "kda_bwd",
                                             flops_bailing_hybrid.KDA_BWD)
