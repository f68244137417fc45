"""Strategy -> plan: the seconds ``models/moe.py`` ``balance_expert_bias``
took to level a fresh router's loads by the balancing rule (counter
``setup.expert_bias_balance_s``, inclusive of its one program's trace and
compile; the fenced forward passes it ran, ``setup.expert_bias_passes``, on
standard error). Moves ``setup_s``. None from a program that does not book
it, and in a cell whose model has no such bias."""

from benchmark import harness, program_counters


def read(record):
    seconds = program_counters.value("setup.expert_bias_balance_s")
    if seconds is not None:
        harness.log(f"setup.expert_bias_balance_s {seconds:.3f} in "
                    f"{program_counters.value('setup.expert_bias_passes')} "
                    f"pass(es), self "
                    f"{program_counters.value('setup.expert_bias_balance_s.self')}")
    return seconds
