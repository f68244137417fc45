"""Sharding: the milliseconds an optimizer step spends, a chip, in the
all-gathers of parameters that are stored as shares over the data axis
(``strategy.FullySharded``): the self seconds the traced window holds under
the instructions named ``all-gather*`` / ``all_gather*``, all chips, over the
traced steps and the chips. All-gathers of one chip do not overlap each other,
so their self seconds are their union. That is the part of the gathers the
compiler leaves as all-gathers (in ``jamba2-sharded4-16k`` the tied table in
front of the fused head and what it does not fold into a product): most of a
layer's weights reach it as ring steps (``collective-permute``) inside the
product that reads them, which ``collective_ms_per_step`` holds with
everything else and no name tells from a gradient's ring steps. The program
says what one gather of every leaf brings a chip (gauge
``step.param_gather_bytes``, on standard error). Nothing to read for another
family's configuration."""

from benchmark import flops_jamba, harness, program_counters

GATHERS = ("all-gather", "all_gather")


def read(record):
    value = flops_jamba.collective_ms_per_step(record, GATHERS)
    if value is not None:
        harness.log(f"step.param_gather_bytes "
                    f"{program_counters.value('step.param_gather_bytes')}")
    return value
