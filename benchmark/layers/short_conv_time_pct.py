"""Kernels: self seconds of the gated short convolution's two kernels
(``pallas:short_conv_fwd``, ``pallas:short_conv_bwd``) as a share of the
seconds the devices were busy in the traced window, all chips: how much of
the step its only bandwidth-bound kernels are. Nothing to read for another
family's configuration or a program that does not name the kernels."""

from benchmark import flops_lfm2, kernel_parts


def read(record):
    if flops_lfm2.cell_parts(record) is None:
        return None
    trace = record["trace"]
    busy = sum(d.busy_s for d in trace.devices.values())
    measured = kernel_parts.group_seconds(
        trace, flops_lfm2.CONV_FWD + flops_lfm2.CONV_BWD)
    return 100.0 * measured / busy if busy > 0 else None
