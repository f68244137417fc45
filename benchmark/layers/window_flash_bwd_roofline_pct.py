"""Kernels: the least time the chip could take for the flash backward of the
traced steps under the layers' own masks (``benchmark/flops_afmoe.py``
``band_flash_cost``: five products over the band's pairs, eight tensors moved
once, K, V, dK and dV once a KV head), over the self seconds the trace holds
under ``pallas:flash_bwd_dkv`` + ``pallas:flash_bwd_dq``, all chips. Nothing
to read for another family's configuration or a program that names no
kernel."""

from benchmark import flops_afmoe


def read(record):
    return flops_afmoe.roofline_pct(record, "flash_bwd", flops_afmoe.FLASH_BWD)
