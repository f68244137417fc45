"""Device: ``memory_stats()["peak_bytes_in_use"]`` on the fullest chip
after the window, in GiB. Memory sets the micro-batch a cell can hold."""


def read(record):
    peak = record["device"].get("memory_peak_bytes")
    return None if peak is None else peak / 2**30
