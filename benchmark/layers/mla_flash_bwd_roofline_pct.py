"""Kernels: the least time the chip could take for latent attention's flash
backward of the traced steps (``benchmark/flops_deepseek_v3.py``
``mla_flash_cost``: five products over the causal triangle's pairs, three 192
deep and two 128; q, k, v, o, dO read and dq, dk, dv written once, the rotary
key and its gradient once a layer), over the self seconds the trace holds
under ``pallas:flash_bwd_dkv`` + ``pallas:flash_bwd_dq`` — all of the
backward's kernels, whichever schedule runs (one pass holds no
``flash_bwd_dq``; the split path recomputes the score tile in both and pays
for it here) — all chips. Nothing to read for another family's
configuration."""

from benchmark import flops_deepseek_v3, kernel_parts


def read(record):
    return flops_deepseek_v3.roofline_pct(record, "flash_bwd",
                                          kernel_parts.FLASH_BWD)
