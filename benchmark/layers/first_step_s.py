"""Compile + cache: the fenced first call of the step, which compiles or
loads from the persistent cache. Moves ``setup_s``."""


def read(record):
    return record.get("first_step_s")
