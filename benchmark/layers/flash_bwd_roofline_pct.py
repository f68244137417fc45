"""Kernels: the least time the chip could take for flash attention's two
backward kernels (``flash_bwd_dkv`` + ``flash_bwd_dq``) in the traced steps,
five of the algorithm's seven score-sized products (the kernels run seven:
each recomputes the scores and dP) and eight of its twelve tensors
(``benchmark/kernel_parts.py``), over the self seconds the trace holds under
the kernels' own names, all chips. Fails the run where the program names its
kernels and the trace does not."""

from benchmark import kernel_parts


def read(record):
    return kernel_parts.roofline_pct(record, "flash_bwd",
                                     kernel_parts.FLASH_BWD)
