"""Kernels: self seconds of the grouped-matmul kernels (``pallas:moe_gmm_*``)
as a share of the seconds the devices were busy in the traced window, all
chips: how much of the step the experts' products are. Nothing to read in a
program that does not name these kernels."""

from benchmark import flops_moe


def read(record):
    measured = flops_moe.gmm_seconds(record)
    if measured is None:
        return None
    busy = sum(d.busy_s for d in record["trace"].devices.values())
    return 100.0 * measured / busy if busy > 0 else None
