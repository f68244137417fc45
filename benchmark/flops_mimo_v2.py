"""Operations and bytes a MiMo-V2 stack requires, from shapes alone, beside
``benchmark/flops.py``, ``benchmark/flops_moe.py`` and
``benchmark/flops_afmoe.py`` (whose conventions hold: a multiply-add is two
operations, only matrix products are counted, recomputation is not, a band
is counted as the pairs it keeps and never as the triangle or the square).

What this family adds:

* **two kinds of attention layer under other head counts.** A sliding layer's
  query at ``i`` sees ``min(i + 1, 128)`` keys through 64 query heads over 8
  KV heads, a full layer's ``i + 1`` through 64 over 4
  (``flops_afmoe.band_pairs``). The sink is one more logit a (head, query): no
  product, nothing counted.
* **two widths.** ``q.k^T`` runs over keys 192 wide and ``p.v`` over values
  128: the forward's two score-sized products are ``pairs x (192 + 128)``
  multiply-adds a head, the backward's five (the scores again, dP, dV, dQ,
  dK) ``pairs x (3 x 192 + 2 x 128)``; q, dQ are 192 wide at the query heads,
  o, dO 128 wide, k, dK 192 and v, dV 128 at the KV heads, each moved once
  (:func:`two_width_flash_cost`).
* **a 256-wide router over 8 held experts, no shared one**: the held experts
  receive ``top_k x held / width`` = a quarter of a token's row on average.
* **a chip's own copy.** Under ``strategy.FullySharded`` every chip runs the
  whole layer on its own sequence with the gathered weights: the head's table
  and the expert banks are read once A CHIP, so those kernels' least time is
  a chip's, times the chips.

``parts`` splits the Pallas calls of one optimizer step by kernel group; the
readers under ``layers/`` read it, and a test holds that they sum to the
job's ``kernel_cost_per_step``.
"""

import math

from benchmark import flops, flops_moe
from benchmark.flops_afmoe import band_pairs

SWA_FWD = ("flash_sink_fwd",)
SWA_BWD = ("flash_sink_bwd_dkv", "flash_sink_bwd_dq")
FULL_FWD = ("flash_fwd",)
FULL_BWD = ("flash_bwd_dkv", "flash_bwd_dq")
SLIDING = 1


def shape(config: dict) -> dict:
    """The sizes the counts need, from the configuration file."""
    kinds = config["hybrid_layer_pattern"]
    return dict(
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        swa_n_kv_heads=config["swa_num_key_value_heads"],
        head_dim=config["head_dim"], v_head_dim=config["v_head_dim"],
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        router_width=config["router_width"],
        experts_held=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"], vocab_size=config["vocab_size"],
        window=config["sliding_window"],
        n_sliding=sum(k == SLIDING for k in kinds),
        n_full=sum(k != SLIDING for k in kinds),
        n_dense=sum(not m for m in config["moe_layer_freq"]))


def forward_flops_per_token(s: dict, seq_len: int) -> dict:
    """The whole stack, forward, per input position, by part."""
    d, heads = s["d_model"], s["n_heads"]
    keys, values = s["head_dim"], s["v_head_dim"]
    n_layers = s["n_sliding"] + s["n_full"]
    n_expert_layers = n_layers - s["n_dense"]

    def projections(kv_heads):      # q and k at the keys' width, v and o at the values'
        return 2 * d * (heads * keys + kv_heads * keys + kv_heads * values
                        + heads * values)

    pairs = (s["n_sliding"] * band_pairs(seq_len, s["window"])
             + s["n_full"] * band_pairs(seq_len, None))
    one_expert = 3 * 2 * d * s["d_expert"]                 # gate, up, down
    return {
        "projections": (s["n_sliding"] * projections(s["swa_n_kv_heads"])
                        + s["n_full"] * projections(s["n_kv_heads"])),
        # q.k^T over the keys' width and p.v over the values', every query head
        "attention": 2 * heads * (keys + values) * pairs / seq_len,
        "dense_mlp": s["n_dense"] * 3 * 2 * d * s["d_ff"],
        "router": n_expert_layers * 2 * d * s["router_width"],
        "held_experts": n_expert_layers * one_expert
        * s["top_k"] * s["experts_held"] / s["router_width"],
        "head": 2 * d * s["vocab_size"],
    }


def train_flops_per_token(config: dict, seq_len: int) -> float:
    return 3.0 * sum(forward_flops_per_token(shape(config), seq_len).values())


def two_width_flash_cost(*, batch: int, seq_len: int, n_heads: int,
                         n_kv_heads: int, head_dim: int, v_head_dim: int,
                         window, act_bytes: int = 2):
    """``(forward, backward)`` ``flops.KernelCost`` of one flash call under
    the band with keys ``head_dim`` and values ``v_head_dim`` wide (module
    docstring)."""
    pairs = 2.0 * batch * n_heads * band_pairs(seq_len, window)
    rows = float(batch * seq_len * act_bytes)
    q, o = rows * n_heads * head_dim, rows * n_heads * v_head_dim
    k, v = rows * n_kv_heads * head_dim, rows * n_kv_heads * v_head_dim
    return (flops.KernelCost(pairs * (head_dim + v_head_dim), q + k + v + o),
            flops.KernelCost(pairs * (3 * head_dim + 2 * v_head_dim),
                             2 * (q + k + v + o)))


def parts(config: dict, traffic: dict) -> dict:
    """``{"swa_flash_fwd", "swa_flash_bwd", "full_flash_fwd",
    "full_flash_bwd", "gmm", "xent"}`` -> ``flops.KernelCost`` of one
    optimizer step on all chips."""
    s = shape(config)
    calls = traffic["accumulation"]
    chips = math.prod(traffic["mesh"].values())
    seq_len = traffic["seq_len"]
    sequences = traffic["micro_batch"] * chips
    tokens_a_chip = traffic["micro_batch"] * seq_len
    common = dict(batch=sequences, seq_len=seq_len, n_heads=s["n_heads"],
                  head_dim=s["head_dim"], v_head_dim=s["v_head_dim"])
    swa_f, swa_b = two_width_flash_cost(
        n_kv_heads=s["swa_n_kv_heads"], window=s["window"], **common)
    full_f, full_b = two_width_flash_cost(
        n_kv_heads=s["n_kv_heads"], window=None, **common)
    n_expert_layers = s["n_sliding"] + s["n_full"] - s["n_dense"]
    # a chip's own rows against its own (gathered) copy of the banks
    gmm = flops_moe.gmm_cost(
        rows=tokens_a_chip * s["top_k"] * s["experts_held"] / s["router_width"],
        d_model=s["d_model"], d_expert=s["d_expert"],
        n_experts=s["experts_held"]) * chips
    xent = flops.fused_xent_cost(rows=tokens_a_chip, d_model=s["d_model"],
                                 vocab_size=s["vocab_size"]) * chips
    return {
        "swa_flash_fwd": swa_f * (s["n_sliding"] * calls),
        "swa_flash_bwd": swa_b * (s["n_sliding"] * calls),
        "full_flash_fwd": full_f * (s["n_full"] * calls),
        "full_flash_bwd": full_b * (s["n_full"] * calls),
        "gmm": gmm * (n_expert_layers * calls),
        "xent": xent * calls,
    }


def kernel_cost_per_step(config: dict, traffic: dict):
    cost = flops.KernelCost(0.0, 0.0)
    for part in parts(config, traffic).values():
        cost = cost + part
    return cost


def is_cell(record) -> bool:
    """Whether the record is of this family's configuration."""
    return record["cell"].config.get("family") == "mimo_v2"


def cell_parts(record):
    """``parts`` of a traced run's cell, or None where there is nothing to
    read: no device trace, another family's configuration, a program that
    does not name the sink's kernels."""
    from benchmark import kernel_parts
    known = kernel_parts.program_kernel_names()
    if record.get("trace") is None or not is_cell(record) \
            or known is None or not set(SWA_FWD + SWA_BWD) <= set(known):
        return None
    return parts(record["cell"].config, record["cell"].traffic)


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
    whose instruction names start with one of ``prefixes`` (self seconds of
    the traced window, all chips, over the traced steps and the chips); None
    without a device trace or for another family's cell, 0 where the trace
    holds none."""
    trace, steps = record.get("trace"), record.get("trace_steps")
    if trace is None or not steps or not is_cell(record):
        return None
    seconds = sum(value for d in trace.devices.values()
                  for name, value in d.by_group.items()
                  if name.startswith(prefixes))
    return 1e3 * seconds / (steps * len(trace.devices))
