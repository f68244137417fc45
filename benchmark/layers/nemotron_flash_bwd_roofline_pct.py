"""Kernels: the least time the chip could take for the flash backward of the
traced steps at this family's shape (``benchmark/flops_afmoe.py``
``band_flash_cost`` at ``window=None``: five of the seven products over the
causal triangle's pairs, eight tensors moved once, K, V, dK and dV once a KV
head of the 2), over the self seconds the trace holds under
``pallas:flash_bwd_dkv`` + ``pallas:flash_bwd_dq``, all chips. Nothing to read
for another family's configuration."""

from benchmark import flops_afmoe, flops_nemotron_h


def read(record):
    return flops_nemotron_h.roofline_pct(record, "flash_bwd",
                                         flops_afmoe.FLASH_BWD)
