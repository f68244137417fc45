"""Operations and bytes a DeepSeek-V3-family stack with latent attention
requires, from shapes alone, beside ``benchmark/flops.py``,
``benchmark/flops_moe.py`` and ``benchmark/flops_afmoe.py`` (whose conventions
hold: a multiply-add is two operations, only matrix products are counted,
recomputation is not, a causal mask halves the score and value products).

What this family adds:

* **latent attention's projections**: the query's ``d x H (d_n + d_r)``, the
  down projection ``d x (r + d_r)``, the up projection ``r x H (d_n + d_v)``
  and the output's ``H d_v x d``. The *absorbed* form (the up projection
  folded into the query and the output) is decode's and is not what a
  training step computes; it is not counted.
* **the core at two widths**: the score product is ``d_n + d_r`` deep and
  the value product ``d_v``, so a (query, key) pair costs ``d_qk + d_v``
  multiply-adds forward (q.k^T, p.v) and ``3 d_qk + 2 d_v`` backward (the
  scores again, dQ, dK; dP, dV): ``mla_flash_cost``. Bytes, each tensor once:
  forward q, a head's ``d_n`` key columns, v read and o written, and the
  ``d_r`` rotary columns **once a layer**, not once a head; backward those
  with dO read and dq, dk, dv and the rotary key's gradient written.
* **the share** (``flops_afmoe.held_rows``), the grouped products
  (``flops_moe.gmm_cost`` at 2,048 x 768) and **the sliced head** as the
  other share configurations count them.

Under per-layer recomputation (``assumed.remat``) the step runs a layer's
forward kernels twice unless the layer keeps their results
(``models/deepseek_v3.py`` ``KEPT``); the counts here are of the required
work, once.

``parts`` splits the Pallas calls of one optimizer step by kernel group; the
readers under ``layers/`` read it.
"""

import math

from benchmark import flops, flops_afmoe, flops_moe

FAMILY = "deepseek_v3"


def shape(config: dict) -> dict:
    """The sizes the counts need, from the configuration file."""
    n_layers, n_dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    return dict(
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        d_nope=config["qk_nope_head_dim"], d_rope=config["qk_rope_head_dim"],
        d_v=config["v_head_dim"], rank=config["kv_lora_rank"],
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        n_shared=config["n_shared_experts"],
        router_width=config["router_width"],
        experts_held=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"], vocab_size=config["vocab_size"],
        n_layers=n_layers, n_dense=n_dense, n_expert=n_layers - n_dense)


def forward_flops_per_token(s: dict, seq_len: int) -> dict:
    """The whole stack, forward, per input position, by part."""
    d, heads = s["d_model"], s["n_heads"]
    d_qk = s["d_nope"] + s["d_rope"]
    one_expert = 3 * 2 * d * s["d_expert"]                  # gate, up, down
    return {
        "projections": s["n_layers"] * 2 * (
            d * heads * d_qk + d * (s["rank"] + s["d_rope"])
            + s["rank"] * heads * (s["d_nope"] + s["d_v"])
            + heads * s["d_v"] * d),
        # q.k^T at the key's width and p.v at the value's, under the causal
        # mask: on average half the sequence
        "attention": s["n_layers"] * seq_len * heads * (d_qk + s["d_v"]),
        "dense_mlp": s["n_dense"] * 3 * 2 * d * s["d_ff"],
        "router": s["n_expert"] * 2 * d * s["router_width"],
        "shared_experts": s["n_expert"] * s["n_shared"] * one_expert,
        "held_experts": s["n_expert"] * one_expert
        * s["top_k"] * s["experts_held"] / s["router_width"],
        "head": 2 * d * s["vocab_size"],
    }


def train_flops_per_token(config: dict, seq_len: int) -> float:
    return 3.0 * sum(forward_flops_per_token(shape(config), seq_len).values())


def mla_flash_cost(*, batch: int, seq_len: int, n_heads: int, d_nope: int,
                   d_rope: int, d_v: int, act_bytes: int = 2):
    """``(forward, backward)`` ``flops.KernelCost`` of one latent-attention
    flash call under the causal mask."""
    pairs = 2.0 * batch * n_heads * flops_afmoe.band_pairs(seq_len, None)
    d_qk = d_nope + d_rope
    rows = batch * seq_len * act_bytes
    q, k_head, v = rows * n_heads * d_qk, rows * n_heads * d_nope, rows * n_heads * d_v
    k_shared = rows * d_rope
    return (flops.KernelCost(pairs * (d_qk + d_v), float(q + k_head + k_shared + 2 * v)),
            flops.KernelCost(pairs * (3 * d_qk + 2 * d_v),
                             float(2 * (q + k_head + k_shared) + 4 * v)))


def parts(config: dict, traffic: dict) -> dict:
    """``{"flash_fwd", "flash_bwd", "gmm", "xent"}`` -> ``flops.KernelCost``
    of one optimizer step on all chips."""
    s = shape(config)
    calls = traffic["accumulation"]
    micro = traffic["micro_batch"] * math.prod(traffic["mesh"].values())
    seq_len = traffic["seq_len"]
    tokens = micro * seq_len
    flash_f, flash_b = mla_flash_cost(
        batch=micro, seq_len=seq_len, n_heads=s["n_heads"], d_nope=s["d_nope"],
        d_rope=s["d_rope"], d_v=s["d_v"])
    gmm = flops_moe.gmm_cost(
        rows=flops_afmoe.held_rows(tokens, s), d_model=s["d_model"],
        d_expert=s["d_expert"], n_experts=s["experts_held"])
    xent = flops.fused_xent_cost(rows=tokens, d_model=s["d_model"],
                                 vocab_size=s["vocab_size"])
    return {
        "flash_fwd": flash_f * (s["n_layers"] * calls),
        "flash_bwd": flash_b * (s["n_layers"] * calls),
        "gmm": gmm * (s["n_expert"] * calls),
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
    does not name its kernels."""
    from benchmark import kernel_parts
    cell = record["cell"]
    if record.get("trace") is None or cell.config.get("family") != FAMILY \
            or kernel_parts.program_kernel_names() is None:
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
