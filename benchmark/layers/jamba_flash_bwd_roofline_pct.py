"""Kernels: as ``jamba_flash_fwd_roofline_pct`` for the flash backward
(``flash_bwd_dkv``, ``flash_bwd_dq``): five of the seven products over the
causal triangle's pairs and eight tensors moved once, dK and dV once for the
one KV head."""

from benchmark import flops_afmoe, flops_jamba


def read(record):
    return flops_jamba.roofline_pct(record, "flash_bwd", flops_afmoe.FLASH_BWD)
