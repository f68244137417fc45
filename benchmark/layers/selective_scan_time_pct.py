"""Kernels: self seconds of the selective scan's two kernels
(``pallas:selective_scan_fwd``, ``pallas:selective_scan_bwd``) and of the two
of the convolution before it (``pallas:conv_silu_fwd``,
``pallas:conv_silu_bwd``) as a share of the seconds the devices were busy in
the traced window, all chips: how much of the step the recurrence is, which is
0.2% of its required operations. Nothing to read for another family's
configuration or a program that does not name the kernels."""

from benchmark import flops_jamba, kernel_parts


def read(record):
    if flops_jamba.cell_parts(record) is None:
        return None
    trace = record["trace"]
    busy = sum(d.busy_s for d in trace.devices.values())
    measured = kernel_parts.group_seconds(
        trace, flops_jamba.SCAN_FWD + flops_jamba.SCAN_BWD
        + flops_jamba.CONV_FWD + flops_jamba.CONV_BWD)
    return 100.0 * measured / busy if busy > 0 else None
