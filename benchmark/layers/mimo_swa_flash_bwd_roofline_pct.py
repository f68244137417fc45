"""Kernels: the same for the sliding layers' flash backward
(``flash_sink_bwd_dkv``, and ``flash_sink_bwd_dq`` where the backward runs as
two kernels): five score-sized products over the band's visible pairs,
``pairs x (3 x 192 + 2 x 128)`` multiply-adds a head, eight tensors moved
once. ``d sink`` is elementwise work in XLA outside these names. Nothing to
read for another family's configuration."""

from benchmark import flops_mimo_v2


def read(record):
    return flops_mimo_v2.roofline_pct(record, "swa_flash_bwd",
                                      flops_mimo_v2.SWA_BWD)
