"""Sharding: the milliseconds an optimizer step spends, a chip, in the
all-gathers of parameters stored as quarters over the data axis
(``strategy.FullySharded``): the self seconds the traced window holds under
the instructions named ``all-gather*`` / ``all_gather*``, all chips, over the
traced steps and the chips. In this cell that holds the expert banks, which
``per_device``'s body gathers as bfloat16 ahead of the share (a
``shard_map``'s ``all_gather``, never folded into a product: the kernels
that read them are Mosaic's), beside the head's table and whatever else the
compiler leaves as a plain all-gather; weights it folds into the products
that read them are ring steps under ``collective_ms_per_step``. The program
says what one gather of every leaf brings a chip (gauge
``step.param_gather_bytes``, on standard error). Nothing to read for another
family's configuration."""

from benchmark import flops_mimo_v2, harness, program_counters

GATHERS = ("all-gather", "all_gather")


def read(record):
    value = flops_mimo_v2.collective_ms_per_step(record, GATHERS)
    if value is not None:
        harness.log(f"step.param_gather_bytes "
                    f"{program_counters.value('step.param_gather_bytes')}")
    return value
