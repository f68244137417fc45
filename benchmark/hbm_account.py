"""The program's HBM account as the readers of ``layers/hbm_*.py`` take it:
gauges ``train.hbm.*`` (booked at the loop's fenced log boundaries) and
``step.hbm.*`` (the compiled step's own
``memory_analysis()``, once a signature), all bytes ONE chip holds, read on
the fullest chip (``autodist_tpu/telemetry/memplane.py``, "HBM account").

A number is given only where the program read a chip's allocator in this
run: after a CPU rehearsal, an untraced run, or a program without the
account, ``train.hbm.resident_bytes`` was never booked and every reader
returns None (the compiler's count alone is no device's reading)."""

from benchmark import harness, program_counters

GIB = 2 ** 30


def booked() -> bool:
    return program_counters.value("train.hbm.resident_bytes") is not None


def gib(name: str):
    """The gauge in GiB, or None where the account or the gauge is absent."""
    value = program_counters.value(name) if booked() else None
    return None if value is None else value / GIB


def say(prefix: str, *names: str):
    """The gauges and counters beside a reader's own, on standard error."""
    parts = []
    for name in names:
        value = program_counters.value(name)
        if value is None:
            continue
        parts.append(f"{name} {value / GIB:.3f} GiB" if name.endswith("_bytes")
                     else f"{name} {value:.6g}")
    if parts:
        harness.log(f"{prefix}: " + ", ".join(parts))
