"""Kernels: the least time the chip could take for the flash forward kernel
(``flash_fwd``) of the traced steps at this family's shape (32 query heads
over 8 KV heads of 64, causal, no window), over the self seconds the trace
holds under the kernel's name, all chips. The least time is
``benchmark/flops_afmoe.py`` ``band_flash_cost`` at ``window=None``: two of
the algorithm's seven products over the causal triangle's (query, key) pairs
and four tensors moved once, K and V once a KV head, summed over the
configuration's attention layers. Nothing to read for another family's
configuration."""

from benchmark import flops_afmoe, flops_lfm2


def read(record):
    return flops_lfm2.roofline_pct(record, "flash_fwd", flops_afmoe.FLASH_FWD)
