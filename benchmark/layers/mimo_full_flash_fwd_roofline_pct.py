"""Kernels: the least time the chip could take for the two full layers' flash
forward (``flash_fwd``: the causal triangle, 64 query heads over 4 KV heads,
keys 192 over values 128, no sink) in the traced steps, over the self seconds
the trace holds under that name, all chips
(``benchmark/flops_mimo_v2.py`` ``two_width_flash_cost`` at ``window=None``).
Nothing to read for another family's configuration."""

from benchmark import flops_mimo_v2


def read(record):
    return flops_mimo_v2.roofline_pct(record, "full_flash_fwd",
                                      flops_mimo_v2.FULL_FWD)
