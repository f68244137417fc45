"""Kernels: the (query, key) pairs a sliding layer's mask keeps as a share of
the pairs in the score tiles the flash forward's walk runs, in percent, from
the program's own trace-time count (gauges ``attn.band_pairs_visible`` over
``attn.band_pairs_computed``, of every sliding layer and head of a step:
``ops/flash_attention.py`` ``band_pairs``). A window of 128 under 512 x 512
tiles touches two key tiles a q block (the band's lower edge crosses one, the
diagonal the other) and keeps 128 of their 1,024 keys a query: 12.8 at 8,192
positions. What a change that fits the tiles to the band would raise.
Nothing to read for another family's configuration or a program without the
gauges."""

from benchmark import flops_mimo_v2, program_counters


def read(record):
    if not flops_mimo_v2.is_cell(record):
        return None
    visible = program_counters.value("attn.band_pairs_visible")
    computed = program_counters.value("attn.band_pairs_computed")
    if not visible or not computed:
        return None
    return 100.0 * visible / computed
