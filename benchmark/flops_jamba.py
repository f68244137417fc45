"""Operations and bytes a Jamba stack requires, from shapes alone, beside
``benchmark/flops.py``, ``benchmark/flops_afmoe.py`` and
``benchmark/flops_nemotron_h.py`` (whose conventions hold: a multiply-add is
two operations, recomputation is not counted, a causal mask halves the score
and value products).

What this family adds:

* **the Mamba-1 layer** is four projections (``d x 2E`` in, ``E x (R + 2N)``
  to the step's rank and ``B`` / ``C``, ``R x E`` to the step, ``E x d`` out)
  around a depthwise convolution, which has no matrix product, and the
  selective scan. The scan has no matrix product either: its ``E N`` state
  elements a token take three multiply-adds each (the decay, ``dt x B`` added,
  the sum with ``C``), which ``scan_flops_per_token`` counts towards the
  model's required operations (0.2% of them) and ``selective_scan_cost``
  does **not** count towards the kernel's least time: a roofline share is
  measured against the MXU's peak or the memory's bandwidth, and this kernel
  has nothing for the MXU. Its least time is its bytes alone, each operand
  once: forward ``x`` read and ``y`` written at the activation's two bytes,
  ``dt`` read at four, ``B`` and ``C`` (``[T, N]`` float32) read and one
  float32 ``[E, N]`` state a chunk written; backward ``x``, ``dt``, ``dy``
  and the states read, ``dx`` and ``ddt`` written, ``B``, ``C``, ``dB``,
  ``dC``. **The VPU and the EUP bound this kernel** (an ``exp`` and five
  multiply-adds a state element, 81,920 of them a token at 5,120 x 16), so
  its share of that roofline reads low, a few percent: the number says how
  far the recurrence is from being memory-bound, not how well it is written.
  The convolution before it is ``flops_nemotron_h.conv_cost``'s count at
  ``E`` channels.
* **multi-query attention without a window**: ``flops_afmoe.band_flash_cost``
  at ``window=None``, 20 query heads over one KV head.
* **a dense gated MLP in every layer** and **the tied head** over the whole
  vocabulary.

Under per-layer recomputation (``assumed.remat``) the step runs a layer's
forward kernels twice unless the layer keeps what they made; the counts here
are of the required work, once.

``parts`` splits the Pallas calls of one optimizer step by kernel group; the
readers under ``layers/`` read it.
"""

import math

from benchmark import flops, flops_afmoe

SCAN_FWD = ("selective_scan_fwd",)
SCAN_BWD = ("selective_scan_bwd",)
CONV_FWD = ("conv_silu_fwd",)
CONV_BWD = ("conv_silu_bwd",)


def shape(config: dict) -> dict:
    """The sizes the counts need, from the configuration file."""
    layers = config["num_hidden_layers"]
    n_attention = sum(
        i % config["attn_layer_period"] == config["attn_layer_offset"]
        for i in range(layers))
    chunk = config.get("assumed", {}).get("scan_chunk", 128)
    return dict(
        d_model=config["hidden_size"],
        d_inner=config["mamba_expand"] * config["hidden_size"],
        d_state=config["mamba_d_state"], dt_rank=config["mamba_dt_rank"],
        chunk=chunk, n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        n_layers=layers, n_attention=n_attention, n_mamba=layers - n_attention)


def scan_flops_per_token(s: dict) -> float:
    """The recurrence, forward, one layer, per input position: three
    multiply-adds a state element."""
    return 2.0 * 3 * s["d_inner"] * s["d_state"]


def forward_flops_per_token(s: dict, seq_len: int) -> dict:
    """The whole stack, forward, per input position, by part."""
    d, e = s["d_model"], s["d_inner"]
    rank_bc = s["dt_rank"] + 2 * s["d_state"]
    wide, narrow = s["n_heads"] * s["head_dim"], s["n_kv_heads"] * s["head_dim"]
    return {
        "mamba_projections": s["n_mamba"] * 2 * (
            d * 2 * e + e * rank_bc + s["dt_rank"] * e + e * d),
        "scan": s["n_mamba"] * scan_flops_per_token(s),
        # q and out at the query heads' width, k and v at the KV head's
        "projections": s["n_attention"] * 2 * d * (2 * wide + 2 * narrow),
        # q.k^T and p.v under the causal mask: on average half the sequence
        "attention": s["n_attention"] * 2 * seq_len * wide,
        "mlp": s["n_layers"] * 2 * 3 * d * s["d_ff"],
        "head": 2 * d * s["vocab_size"],
    }


def train_flops_per_token(config: dict, seq_len: int) -> float:
    return 3.0 * sum(forward_flops_per_token(shape(config), seq_len).values())


def selective_scan_cost(*, tokens: int, s: dict, act_bytes: int = 2):
    """``(forward, backward)`` ``flops.KernelCost`` of one selective scan
    over ``tokens`` positions: bytes alone (module docstring)."""
    act = float(tokens * s["d_inner"] * act_bytes)
    f32 = float(tokens * s["d_inner"] * 4)
    narrow = float(tokens * s["d_state"] * 4)
    states = float(-(-tokens // s["chunk"]) * s["d_inner"] * s["d_state"] * 4)
    return (flops.KernelCost(0.0, 2 * act + f32 + 2 * narrow + states),
            flops.KernelCost(0.0, 3 * act + 2 * f32 + 4 * narrow + states))


def conv_cost(*, tokens: int, s: dict, act_bytes: int = 2):
    """``(forward, backward)`` of one depthwise convolution with bias and
    SiLU over ``tokens`` positions of ``E`` channels: bytes alone."""
    array = float(tokens * s["d_inner"] * act_bytes)
    return flops.KernelCost(0.0, 2 * array), flops.KernelCost(0.0, 3 * array)


def parts(config: dict, traffic: dict) -> dict:
    """``{"scan_fwd", "scan_bwd", "conv_fwd", "conv_bwd", "flash_fwd",
    "flash_bwd", "xent"}`` -> ``flops.KernelCost`` of one optimizer step on
    all chips."""
    s = shape(config)
    calls = traffic["accumulation"]
    micro = traffic["micro_batch"] * math.prod(traffic["mesh"].values())
    seq_len = traffic["seq_len"]
    tokens = micro * seq_len
    scan_f, scan_b = selective_scan_cost(tokens=tokens, s=s)
    conv_f, conv_b = conv_cost(tokens=tokens, s=s)
    flash_f, flash_b = flops_afmoe.band_flash_cost(
        batch=micro, seq_len=seq_len, n_heads=s["n_heads"],
        n_kv_heads=s["n_kv_heads"], head_dim=s["head_dim"], window=None)
    xent = flops.fused_xent_cost(rows=tokens, d_model=s["d_model"],
                                 vocab_size=s["vocab_size"])
    return {
        "scan_fwd": scan_f * (s["n_mamba"] * calls),
        "scan_bwd": scan_b * (s["n_mamba"] * calls),
        "conv_fwd": conv_f * (s["n_mamba"] * calls),
        "conv_bwd": conv_b * (s["n_mamba"] * calls),
        "flash_fwd": flash_f * (s["n_attention"] * calls),
        "flash_bwd": flash_b * (s["n_attention"] * calls),
        "xent": xent * calls,
    }


def kernel_cost_per_step(config: dict, traffic: dict):
    cost = flops.KernelCost(0.0, 0.0)
    for part in parts(config, traffic).values():
        cost = cost + part
    return cost


def cell_parts(record):
    """``parts`` of a traced run's cell, or None where there is nothing to
    read: no device trace, another family's configuration, a program that
    does not name the scan's kernels."""
    from benchmark import kernel_parts
    cell = record["cell"]
    known = kernel_parts.program_kernel_names()
    if record.get("trace") is None or cell.config.get("family") != "jamba" \
            or known is None or not set(SCAN_FWD + SCAN_BWD) <= set(known):
        return None
    return parts(cell.config, cell.traffic)


def roofline_pct(record, part: str, names):
    """Least seconds of ``part`` for the traced steps over the self seconds
    the trace holds under ``pallas:<name>`` for ``names``, all chips, in
    percent; None where there is nothing to read. A program that names its
    kernels and a trace that holds no time under them is a fault: the run
    fails, as in ``kernel_parts.roofline_pct``."""
    from benchmark import harness, kernel_parts
    steps, peaks = record.get("trace_steps"), record.get("peaks")
    costs = cell_parts(record)
    if costs is None or not steps or peaks is None:
        return None
    measured = kernel_parts.group_seconds(record["trace"], names)
    if measured <= 0:
        raise harness.BenchmarkError(
            f"{record['cell'].name}: the trace holds no time under {names}")
    return 100.0 * costs[part].least_seconds(peaks) * steps / measured


def collective_ms_per_step(record, prefixes):
    """Milliseconds an optimizer step spends, a chip, in the collectives
    whose instruction names start with one of ``prefixes`` (the self seconds
    the traced window holds under them, all chips, over the traced steps and
    the chips; collectives of one kind do not overlap each other on a chip,
    so their self seconds are their union); None without a device trace, 0
    where the trace holds none."""
    trace, steps = record.get("trace"), record.get("trace_steps")
    if trace is None or not steps or cell_parts(record) is None:
        return None
    seconds = sum(value for d in trace.devices.values()
                  for name, value in d.by_group.items()
                  if name.startswith(prefixes))
    return 1e3 * seconds / (steps * len(trace.devices))
