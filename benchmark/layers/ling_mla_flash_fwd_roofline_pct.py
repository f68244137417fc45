"""Kernels: the least time the chip could take for gated latent attention's
flash forward (``flash_fwd``) of the traced steps
(``benchmark/flops_deepseek_v3.py`` ``mla_flash_cost`` at the 16 heads held and
8,192 positions: two products over the causal triangle's (query, key) pairs,
the scores 192 deep and the values 128; q, a head's 128 key columns, v read
and o written once, the 64 rotary columns once a layer), summed over the
configuration's latent layers, once a step, over the self seconds the trace
holds under the kernel's name, all chips. Nothing to read for another family's
configuration."""

from benchmark import flops_bailing_hybrid, kernel_parts


def read(record):
    return flops_bailing_hybrid.roofline_pct(record, "flash_fwd",
                                             kernel_parts.FLASH_FWD)
