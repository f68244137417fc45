"""Kernels: the least time the chip could take for the chunked state-space
scan's backward kernel in the traced steps (``benchmark/flops_nemotron_h.py``
``ssd_cost``: twice the forward's products; ``x``, ``dy``, ``B``, ``C`` and the
chunk states read, ``dx``, ``dB``, ``dC`` written, each once), over the self
seconds the trace holds under ``pallas:ssd_bwd``, all chips. Nothing to read
for another family's configuration or a program that does not name the
kernel."""

from benchmark import flops_nemotron_h


def read(record):
    return flops_nemotron_h.roofline_pct(record, "ssd_bwd",
                                         flops_nemotron_h.SSD_BWD)
