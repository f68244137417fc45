"""Strategy -> plan: ``train()``'s entry to its loop, whose first act is the
first pull from the batch source (counter ``setup.train_enter_s``, inclusive: the second
``runner.init``, the saver, the monitors; its self seconds on standard
error). Moves ``setup_s``. None from a program that does not book it."""

from benchmark import harness, program_counters


def read(record):
    seconds = program_counters.value("setup.train_enter_s")
    if seconds is not None:
        harness.log(f"setup.train_enter_s {seconds:.3f}, self "
                    f"{program_counters.value('setup.train_enter_s.self')}")
    return seconds
