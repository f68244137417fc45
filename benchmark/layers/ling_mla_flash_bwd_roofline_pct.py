"""Kernels: as ``ling_mla_flash_fwd_roofline_pct``, for latent attention's
flash backward (``flash_bwd_dkv`` + ``flash_bwd_dq``: five products over the
causal triangle's pairs, three 192 deep and two 128; q, k, v, o, dO read and
dq, dk, dv and the rotary key's gradient written). Nothing to read for
another family's configuration."""

from benchmark import flops_bailing_hybrid, kernel_parts


def read(record):
    return flops_bailing_hybrid.roofline_pct(record, "flash_bwd",
                                             kernel_parts.FLASH_BWD)
