"""Operations and bytes an LFM2-MoE stack requires, from shapes alone, beside
``benchmark/flops.py``, ``benchmark/flops_moe.py`` and
``benchmark/flops_afmoe.py`` (whose conventions hold: a multiply-add is two
operations, only matrix products are counted, recomputation is not, a causal
mask halves the score and value products).

What this family adds:

* **the conv operator** is two projections (``d x 3d`` in, ``d x d`` out)
  around a depthwise convolution that has no matrix product: its required
  operations are the projections', and its kernels are counted in bytes
  alone. ``short_conv_cost``: the forward reads the three thirds of ``bcu``
  and writes ``y`` (8 bytes an element of ``[T, d]`` at two bytes each), the
  backward reads ``bcu`` and ``dy`` and writes ``dbcu`` (14), each operand
  once; the taps and their gradient (``K x d`` float32) are nothing beside
  them and left out, so the share is never over-stated.
* **grouped KV heads without a window**: ``flops_afmoe.band_flash_cost`` at
  ``window=None`` (the exact causal triangle, K and V once a KV head).
* **the share**: of the router's ``top_k`` choices a token the experts held
  here receive ``top_k x held / width`` on average; there is no shared expert.
* **the tied, sliced head**: the rows of the vocabulary held here, once.

``parts`` splits the Pallas calls of one optimizer step by kernel group; the
readers under ``layers/`` read it.
"""

import math

from benchmark import flops, flops_afmoe, flops_moe

CONV_FWD = ("short_conv_fwd",)
CONV_BWD = ("short_conv_bwd",)
CONV = "conv"


def shape(config: dict) -> dict:
    """The sizes the counts need, from the configuration file."""
    kinds = config["layer_types"]
    return dict(
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        d_ff=config["intermediate_size"], d_expert=config["moe_intermediate_size"],
        router_width=config["router_width"], experts_held=config["num_experts"],
        top_k=config["num_experts_per_tok"], vocab_size=config["vocab_size"],
        n_dense=config["num_dense_layers"],
        n_conv=sum(k == CONV for k in kinds),
        n_attention=sum(k != CONV for k in kinds))


def forward_flops_per_token(s: dict, seq_len: int) -> dict:
    """The whole stack, forward, per input position, by part."""
    d = s["d_model"]
    wide, narrow = s["n_heads"] * s["head_dim"], s["n_kv_heads"] * s["head_dim"]
    n_expert_layers = s["n_conv"] + s["n_attention"] - s["n_dense"]
    one_expert = 3 * 2 * d * s["d_expert"]                  # gate, up, down
    return {
        # in_proj d x 3d and out_proj d x d; the convolution between them has
        # no matrix product
        "conv_operators": s["n_conv"] * 2 * d * (3 * d + d),
        # q and out at the query heads' width, k and v at the KV heads'
        "projections": s["n_attention"] * 2 * d * (2 * wide + 2 * narrow),
        # q.k^T and p.v under the causal mask: on average half the sequence
        "attention": s["n_attention"] * 2 * seq_len * wide,
        "dense_mlp": s["n_dense"] * 3 * 2 * d * s["d_ff"],
        "router": n_expert_layers * 2 * d * s["router_width"],
        "held_experts": n_expert_layers * one_expert
        * s["top_k"] * s["experts_held"] / s["router_width"],
        "head": 2 * d * s["vocab_size"],
    }


def train_flops_per_token(config: dict, seq_len: int) -> float:
    return 3.0 * sum(forward_flops_per_token(shape(config), seq_len).values())


def short_conv_cost(*, tokens: int, d_model: int, act_bytes: int = 2):
    """``(forward, backward)`` ``flops.KernelCost`` of one gated short
    convolution over ``tokens`` rows: no matrix product; 4 tensors of
    ``[tokens, d]`` moved forward (B, C, u read, y written), 7 backward (B,
    C, u, dy read, dB, dC, du written)."""
    tensor = float(tokens * d_model * act_bytes)
    return flops.KernelCost(0.0, 4 * tensor), flops.KernelCost(0.0, 7 * tensor)


def parts(config: dict, traffic: dict) -> dict:
    """``{"conv_fwd", "conv_bwd", "flash_fwd", "flash_bwd", "gmm", "xent"}``
    -> ``flops.KernelCost`` of one optimizer step on all chips."""
    s = shape(config)
    calls = traffic["accumulation"]
    micro = traffic["micro_batch"] * math.prod(traffic["mesh"].values())
    seq_len = traffic["seq_len"]
    tokens = micro * seq_len
    conv_f, conv_b = short_conv_cost(tokens=tokens, d_model=s["d_model"])
    flash_f, flash_b = flops_afmoe.band_flash_cost(
        batch=micro, seq_len=seq_len, n_heads=s["n_heads"],
        n_kv_heads=s["n_kv_heads"], head_dim=s["head_dim"], window=None)
    n_expert_layers = s["n_conv"] + s["n_attention"] - s["n_dense"]
    gmm = flops_moe.gmm_cost(
        rows=flops_afmoe.held_rows(tokens, s), d_model=s["d_model"],
        d_expert=s["d_expert"], n_experts=s["experts_held"])
    xent = flops.fused_xent_cost(rows=tokens, d_model=s["d_model"],
                                 vocab_size=s["vocab_size"])
    return {
        "conv_fwd": conv_f * (s["n_conv"] * calls),
        "conv_bwd": conv_b * (s["n_conv"] * calls),
        "flash_fwd": flash_f * (s["n_attention"] * calls),
        "flash_bwd": flash_b * (s["n_attention"] * calls),
        "gmm": gmm * (n_expert_layers * calls),
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
    does not name the convolution's kernels."""
    from benchmark import kernel_parts
    cell = record["cell"]
    known = kernel_parts.program_kernel_names()
    if record.get("trace") is None or cell.config.get("family") != "lfm2_moe" \
            or known is None or not set(CONV_FWD + CONV_BWD) <= set(known):
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
