"""Kernels: the least time the chip could take for the selective scan's
forward kernel in the traced steps, over the self seconds the trace holds
under ``pallas:selective_scan_fwd``, all chips. The least time is
``benchmark/flops_jamba.py`` ``selective_scan_cost``: the kernel's bytes at the
memory bandwidth (``x`` read and ``y`` written at two bytes an element, ``dt``
read at four, ``B`` and ``C`` read, one float32 ``[E, N]`` state a chunk
written, each once) and no product for the MXU, summed over the
configuration's Mamba-1 layers, once a step. The VPU and the EUP bound this
kernel (an ``exp`` and five multiply-adds for each of 81,920 state elements a
token), so the share reads low, a few percent, and under per-layer
recomputation the kernel runs twice a step unless the layer keeps its result
(and once more, on float32 operands, for the first layer's precise value).
Nothing to read for another family's configuration or a program that does not
name the kernel."""

from benchmark import flops_jamba


def read(record):
    return flops_jamba.roofline_pct(record, "scan_fwd", flops_jamba.SCAN_FWD)
