"""Strategy -> plan: the seconds ``ShardingPlan.from_strategy`` took, every
call (counter ``setup.plan_build_s``). Moves ``setup_s``. None from a program
that does not book it."""

from benchmark import program_counters


def read(record):
    return program_counters.value("setup.plan_build_s")
