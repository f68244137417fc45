"""Kernels: the least time the chip could take for the flash forward kernel
(``flash_fwd``) of the traced steps under the layers' own masks, over the self
seconds the trace holds under the kernel's name, all chips. The least time is
``benchmark/flops_afmoe.py`` ``band_flash_cost``: the two forward products over
the band's (query, key) pairs (the window in a sliding layer, the triangle in
a full one) and four tensors moved once, K and V once a KV head, summed over
the configuration's sliding and full layers. Nothing to read for another
family's configuration or a program that names no kernel."""

from benchmark import flops_afmoe


def read(record):
    return flops_afmoe.roofline_pct(record, "flash_fwd", flops_afmoe.FLASH_FWD)
