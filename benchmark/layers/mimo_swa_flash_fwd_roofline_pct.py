"""Kernels: the least time the chip could take for the sliding layers' flash
forward (``flash_sink_fwd``: window 128, a sink a head, 64 query heads over 8
KV heads, keys 192 over values 128) in the traced steps, over the self
seconds the trace holds under that name, all chips. The least time is
``benchmark/flops_mimo_v2.py`` ``two_width_flash_cost``: the two forward
products over the band's visible (query, key) pairs, ``pairs x (192 + 128)``
multiply-adds a head, and q, k, v, o moved once (K and V once a KV head). The
kernel computes every pair of the 512 x 512 tiles the band touches, eight
times the visible ones (``mimo_swa_tile_fill_pct``), so this share reads a
few percent by construction until the tiles fit the band. Nothing to read for
another family's configuration."""

from benchmark import flops_mimo_v2


def read(record):
    return flops_mimo_v2.roofline_pct(record, "swa_flash_fwd",
                                      flops_mimo_v2.SWA_FWD)
