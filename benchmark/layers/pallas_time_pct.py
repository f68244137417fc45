"""Kernels: self time of the Mosaic custom calls (flash attention and the
fused head + loss, counted together: no ``pallas_call`` carries a name yet)
as a share of the traced window, averaged over the devices. 0 where the
configuration bypasses both kernels."""


def read(record):
    trace = record.get("trace")
    if trace is None:
        return None
    return 100.0 * trace.mean("pallas_s") / trace.window_s
