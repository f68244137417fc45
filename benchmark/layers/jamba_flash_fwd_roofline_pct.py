"""Kernels: the least time the chip could take for the flash forward kernel
(``flash_fwd``) of the traced steps at this family's shape (20 query heads
over ONE KV head of 128, causal, no window, no rotary turn, 16,384 positions),
over the self seconds the trace holds under the kernel's name, all chips. The
least time is ``benchmark/flops_afmoe.py`` ``band_flash_cost`` at
``window=None``: two of the algorithm's seven products over the causal
triangle's (query, key) pairs and four tensors moved once, K and V once for
the one KV head, summed over the configuration's attention layers, once a step
(under per-layer recomputation the kernel runs twice unless the layer keeps
its result). Nothing to read for another family's configuration."""

from benchmark import flops_afmoe, flops_jamba


def read(record):
    return flops_jamba.roofline_pct(record, "flash_fwd", flops_afmoe.FLASH_FWD)
