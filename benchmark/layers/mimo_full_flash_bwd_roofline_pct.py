"""Kernels: the same for the two full layers' flash backward
(``flash_bwd_dkv``; ``flash_bwd_dq`` where it runs as two kernels): five
score-sized products over the causal triangle at 192 / 128. Nothing to read
for another family's configuration."""

from benchmark import flops_mimo_v2


def read(record):
    return flops_mimo_v2.roofline_pct(record, "full_flash_bwd",
                                      flops_mimo_v2.FULL_BWD)
