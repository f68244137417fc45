"""Device: what the fullest chip holds at a fenced log boundary, the device
idle between two steps, in GiB (gauge ``train.hbm.resident_bytes``: the
allocator's ``bytes_in_use``; the state's share of it, the limit, and the
allocator's lifetime peak with its rise since ``train()``'s first pull, 0
where set-up made the process's peak, on standard error). None where the
program read no allocator (``benchmark/hbm_account.py``)."""

from benchmark import hbm_account


def read(record):
    value = hbm_account.gib("train.hbm.resident_bytes")
    if value is not None:
        hbm_account.say("hbm at the boundary", "train.hbm.state_bytes",
                        "train.hbm.limit_bytes",
                        "train.hbm.allocator_peak_bytes",
                        "train.hbm.allocator_peak_rise_bytes")
    return value
