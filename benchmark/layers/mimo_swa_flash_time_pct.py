"""Kernels: self seconds of the three sink-named flash kernels
(``pallas:flash_sink_fwd``, ``pallas:flash_sink_bwd_dkv``,
``pallas:flash_sink_bwd_dq``) as a share of the seconds the devices were
busy in the traced window, all chips: how much of the step the five
window-128 layers' attention cores are. Nothing to read for another family's
configuration or a program that does not name these kernels."""

from benchmark import flops_mimo_v2, kernel_parts


def read(record):
    if flops_mimo_v2.cell_parts(record) is None:
        return None
    trace = record["trace"]
    busy = sum(d.busy_s for d in trace.devices.values())
    measured = kernel_parts.group_seconds(
        trace, flops_mimo_v2.SWA_FWD + flops_mimo_v2.SWA_BWD)
    return 100.0 * measured / busy if busy > 0 else None
