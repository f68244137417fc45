"""Kernels: self seconds of latent attention's flash kernels
(``pallas:flash_fwd``, ``pallas:flash_bwd_dkv``, ``pallas:flash_bwd_dq``) as a
share of the seconds the devices were busy in the traced window, all chips:
how much of the step the attention core is, which is 64% of its required
operations at 16,384 positions. Nothing to read for another family's
configuration or a program that does not name its kernels."""

from benchmark import flops_deepseek_v3, kernel_parts


def read(record):
    if flops_deepseek_v3.cell_parts(record) is None:
        return None
    trace = record["trace"]
    busy = sum(d.busy_s for d in trace.devices.values())
    measured = kernel_parts.group_seconds(
        trace, kernel_parts.FLASH_FWD + kernel_parts.FLASH_BWD)
    return 100.0 * measured / busy if busy > 0 else None
