"""Kernels: self seconds of the flash kernels (``pallas:flash_fwd``,
``pallas:flash_bwd_dkv``, ``pallas:flash_bwd_dq``) as a share of the seconds
the devices were busy in the traced window, all chips: how much of the step
attention under the window, the grouped heads and the full layer is. Nothing
to read for another family's configuration or a program that names no
kernel."""

from benchmark import flops_afmoe, kernel_parts


def read(record):
    if flops_afmoe.cell_parts(record) is None:
        return None
    trace = record["trace"]
    busy = sum(d.busy_s for d in trace.devices.values())
    measured = kernel_parts.group_seconds(
        trace, flops_afmoe.FLASH_FWD + flops_afmoe.FLASH_BWD)
    return 100.0 * measured / busy if busy > 0 else None
