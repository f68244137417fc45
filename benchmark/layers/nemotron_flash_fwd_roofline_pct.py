"""Kernels: the least time the chip could take for the flash forward kernel
(``flash_fwd``) of the traced steps at this family's shape (32 query heads
over 2 KV heads of 128, causal, no window, 8,192 positions), over the self
seconds the trace holds under the kernel's name, all chips. The least time is
``benchmark/flops_afmoe.py`` ``band_flash_cost`` at ``window=None``: two of
the algorithm's seven products over the causal triangle's (query, key) pairs
and four tensors moved once, K and V once a KV head, summed over the
configuration's attention layers, once a step (under per-layer recomputation
the kernel runs twice). Nothing to read for another family's configuration."""

from benchmark import flops_afmoe, flops_nemotron_h


def read(record):
    return flops_nemotron_h.roofline_pct(record, "flash_fwd",
                                         flops_afmoe.FLASH_FWD)
