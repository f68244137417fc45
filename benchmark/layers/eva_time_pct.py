"""Kernels: self seconds of EVA attention's two kernels (``pallas:eva_fwd``,
``pallas:eva_bwd``) as a share of the seconds the devices were busy in the
traced window, all chips: how much of the step the operator's core is, which
is 2% of its required operations. The pooling of the summaries stays XLA's,
under the named scope ``eva_pool``; the trace's operation names (an
instruction's name, not its scope) do not tell its fusions from the layer's
others, so they are not in this share (PERF.md section 7). Nothing to read
for another family's configuration or a program that does not name the
kernels."""

from benchmark import flops_evabyte, kernel_parts


def read(record):
    if flops_evabyte.cell_parts(record) is None:
        return None
    trace = record["trace"]
    busy = sum(d.busy_s for d in trace.devices.values())
    measured = kernel_parts.group_seconds(
        trace, flops_evabyte.EVA_FWD + flops_evabyte.EVA_BWD)
    return 100.0 * measured / busy if busy > 0 else None
