"""Kernels: the least time the chip could take for flash attention's forward
kernel (``flash_fwd``) in the traced steps, two of the algorithm's seven
score-sized products and four of its twelve tensors
(``benchmark/kernel_parts.py``, from ``benchmark/flops.py`` on the cell's own
configuration and traffic), over the self seconds the trace holds under the
kernel's own name, all chips. Fails the run where the program names its
kernels and the trace does not."""

from benchmark import kernel_parts


def read(record):
    return kernel_parts.roofline_pct(record, "flash_fwd",
                                     kernel_parts.FLASH_FWD)
