"""Kernels: self seconds of the chunked scan's two kernels (``pallas:ssd_fwd``,
``pallas:ssd_bwd``) and of the two of the convolution before it
(``pallas:conv_silu_fwd``, ``pallas:conv_silu_bwd``) as a share of the seconds
the devices were busy in the traced window, all chips: how much of the step
the scan is, which is 1.5% of its required operations. Nothing to read for
another family's configuration or a program that does not name the kernels."""

from benchmark import flops_nemotron_h, kernel_parts


def read(record):
    if flops_nemotron_h.cell_parts(record) is None:
        return None
    trace = record["trace"]
    busy = sum(d.busy_s for d in trace.devices.values())
    measured = kernel_parts.group_seconds(
        trace, flops_nemotron_h.SSD_FWD + flops_nemotron_h.SSD_BWD
        + flops_nemotron_h.CONV_FWD + flops_nemotron_h.CONV_BWD)
    return 100.0 * measured / busy if busy > 0 else None
