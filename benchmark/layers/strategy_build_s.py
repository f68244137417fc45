"""Strategy -> plan: the seconds ``AutoDist.build_strategy`` took, the
strategy file's write included (counter ``setup.strategy_build_s``,
inclusive; ``setup.strategy_write_s`` beside it on standard error). Moves
``setup_s``. None from a program that does not book it."""

from benchmark import harness, program_counters


def read(record):
    seconds = program_counters.value("setup.strategy_build_s")
    if seconds is not None:
        harness.log(f"setup.strategy_build_s {seconds:.3f}, of it "
                    f"setup.strategy_write_s "
                    f"{program_counters.value('setup.strategy_write_s')}")
    return seconds
