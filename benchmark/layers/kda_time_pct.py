"""Kernels: self seconds of Kimi Delta Attention's two kernels
(``pallas:kda_fwd``, ``pallas:kda_bwd``) as a share of the seconds the devices
were busy in the traced window, all chips: how much of the step the
recurrence is, which is 2% of its required operations. What XLA keeps of the
mixer beside them (the two L2 norms, the decay and ``beta``, the output norm
under its gate) sits under the named scopes ``kda_qk_norm``, ``kda_gate`` and
``kda_out_norm``; the trace's operation names (an instruction's name, not its
scope) do not tell their fusions from the layer's others, so they are not in
this share (PERF.md section 7). Nothing to read for another family's
configuration or a program that does not name the kernels."""

from benchmark import flops_bailing_hybrid, kernel_parts


def read(record):
    if flops_bailing_hybrid.cell_parts(record) is None:
        return None
    trace = record["trace"]
    busy = sum(d.busy_s for d in trace.devices.values())
    measured = kernel_parts.group_seconds(
        trace, flops_bailing_hybrid.KDA_FWD + flops_bailing_hybrid.KDA_BWD)
    return 100.0 * measured / busy if busy > 0 else None
