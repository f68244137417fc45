"""Kernels: the milliseconds an optimizer step spends moving rows by index, a
chip: the self seconds the traced window holds under XLA's gathers and
scatters (``fusion (kCustom)``: the routed rows' dispatch and combine with
their transposes, the router's scalar ones, the embedding's) and under the two
kernels that take such work over where the program has them
(``pallas:moe_rows_gather``, ``pallas:moe_rows_combine``; a program without
them holds no time there), all chips, over the traced steps and the chips. The
same work on both sides of a pair, whichever moves it. Nothing to read without
a device trace."""

from benchmark import kernel_parts

XLA_ROW_MOVES = "fusion (kCustom)"
ROW_KERNELS = ("moe_rows_gather", "moe_rows_combine")


def read(record):
    trace, steps = record.get("trace"), record.get("trace_steps")
    if trace is None or not steps:
        return None
    seconds = sum(d.by_group.get(XLA_ROW_MOVES, 0.0)
                  for d in trace.devices.values()) \
        + kernel_parts.group_seconds(trace, ROW_KERNELS)
    return 1e3 * seconds / (steps * len(trace.devices))
