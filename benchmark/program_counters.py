"""The program's own registry, still live in the process after the run."""


def value(name: str):
    """The counter's value, or None where the program never booked it (a
    program older than the counter): asking must not create it."""
    from autodist_tpu import telemetry
    instrument = telemetry.registry().get(name)
    return None if instrument is None else instrument.value
