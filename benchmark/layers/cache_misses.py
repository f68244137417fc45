"""Compile + cache: programs the persistent cache did not hold in this run
(jax.monitoring's ``cache_misses`` events, set-up and window together).
From a cell's second run in a checkout on this must read 0. Moves
``setup_s``."""


def read(record):
    return record.get("cache_misses")
