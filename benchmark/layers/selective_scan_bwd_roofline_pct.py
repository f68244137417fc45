"""Kernels: as ``selective_scan_fwd_roofline_pct`` for the backward kernel
(``pallas:selective_scan_bwd``): ``x``, ``dt``, ``dy`` and the chunks' states
read, ``dx`` and ``ddt`` written, ``B``, ``C``, ``dB``, ``dC``, each once, at
the memory bandwidth, over the kernel's self seconds, all chips. The kernel
makes every chunk's states again and walks them in reverse (some four times
the forward's arithmetic on the VPU), so the share reads lower than the
forward's."""

from benchmark import flops_jamba


def read(record):
    return flops_jamba.roofline_pct(record, "scan_bwd", flops_jamba.SCAN_BWD)
