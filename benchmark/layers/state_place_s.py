"""Strategy -> plan: the seconds the program spent placing train states on
the mesh (``setup.state_place_s``, every ``runner.init``: once under
``AutoDist.function`` and once more under ``train()``; the call count goes
to standard error). Moves ``setup_s``. None from a program that does not
book it."""

from benchmark import harness, program_counters


def read(record):
    seconds = program_counters.value("setup.state_place_s")
    calls = program_counters.value("setup.state_place_calls")
    if not calls:
        return None
    harness.log(f"setup.state_place_s {seconds:.3f} in {calls} call(s); "
                f"setup.strategy_build_s "
                f"{program_counters.value('setup.strategy_build_s')}, "
                f"setup.plan_build_s "
                f"{program_counters.value('setup.plan_build_s')}")
    return seconds
