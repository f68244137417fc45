"""Device: what the compiled step says it needs on a device, arguments
plus temporaries plus outputs less what is aliased, in GiB
(``compiled.memory_analysis()``)."""

from benchmark import harness


def read(record):
    b = record.get("compiled", {}).get("compiled_bytes")
    return harness.step_bytes(b) / 2**30 if b else None
