"""Operations and bytes a Nemotron-H stack requires, from shapes alone, beside
``benchmark/flops.py``, ``benchmark/flops_moe.py`` and
``benchmark/flops_afmoe.py`` (whose conventions hold: a multiply-add is two
operations, only matrix products are counted, recomputation is not, a causal
mask halves the score and value products).

What this family adds:

* **the Mamba-2 layer** is two projections (``d x (2 d_inner + 2 G N + H)``
  in, ``d_inner x d`` out) around a depthwise convolution, which has no
  matrix product, and the scan. The scan is counted as the chunked algorithm
  defines it (``ssd_cost``), chunk ``Q``: a group's ``C B^T`` over the causal
  half of a chunk's pairs (``Q N / 2`` multiply-adds a token and group), and
  per head the masked plane against ``dt x`` (``Q P / 2``), the state that
  enters the chunk against ``C`` (``N P``) and the chunk's own addition to it
  (``P N``). Its backward is twice that (each product transposed twice; the
  planes it builds again are recomputation and not counted). Bytes, each
  operand once: forward ``x`` and ``y`` (``[T, H P]``), ``B`` and ``C``
  (``[T, G N]``) at the activation's two bytes and one float32 ``[P, N]``
  state a chunk and head written; backward ``x``, ``dy``, ``dx``, ``B``,
  ``C``, ``dB``, ``dC`` and the states read. ``dt`` and the running sums
  (``[T, H]`` float32) are nothing beside them and left out, so a share is
  never over-stated. The convolution before it (``conv_cost``) has no
  product at all: ``[T, d_inner + 2 G N]`` read and written forward, ``x`` and
  ``dy`` read and ``dx`` written backward, at the activation's two bytes (a
  layer that computes in float32 moves more, which only lowers its share).
* **relu2 experts**: two banks, no gate: two products forward and four
  backward a held row (``relu2_gmm_cost``), the shared expert every token.
* **grouped KV heads without a window**: ``flops_afmoe.band_flash_cost`` at
  ``window=None``.
* **the share** and **the sliced head** as ``flops_afmoe`` counts them.

Under per-layer recomputation (``assumed.remat``) the step runs every
layer's forward kernels twice; the counts here are of the required work, once,
so a forward kernel's share of its roofline then reads at most half of what
the kernel reaches on a call.

``parts`` splits the Pallas calls of one optimizer step by kernel group; the
readers under ``layers/`` read it.
"""

import math

from benchmark import flops, flops_afmoe

SSD_FWD = ("ssd_fwd",)
SSD_BWD = ("ssd_bwd",)
CONV_FWD = ("conv_silu_fwd",)
CONV_BWD = ("conv_silu_bwd",)
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def shape(config: dict) -> dict:
    """The sizes the counts need, from the configuration file."""
    pattern = config["hybrid_override_pattern"]
    return dict(
        d_model=config["hidden_size"], mamba_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"], n_groups=config["n_groups"],
        d_state=config["ssm_state_size"], chunk=config["chunk_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["moe_shared_expert_intermediate_size"]
        * config["n_shared_experts"],
        router_width=config["router_width"],
        experts_held=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"], vocab_size=config["vocab_size"],
        n_mamba=pattern.count(MAMBA), n_expert=pattern.count(EXPERTS),
        n_attention=pattern.count(ATTENTION))


def scan_flops_per_token(s: dict) -> float:
    """The chunked scan, forward, one layer, per input position."""
    q, n, p = s["chunk"], s["d_state"], s["mamba_head_dim"]
    per_group = q * n / 2
    per_head = q * p / 2 + 2 * n * p
    return 2.0 * (s["n_groups"] * per_group + s["mamba_heads"] * per_head)


def forward_flops_per_token(s: dict, seq_len: int) -> dict:
    """The whole stack, forward, per input position, by part."""
    d = s["d_model"]
    d_inner = s["mamba_heads"] * s["mamba_head_dim"]
    in_width = 2 * d_inner + 2 * s["n_groups"] * s["d_state"] + s["mamba_heads"]
    wide, narrow = s["n_heads"] * s["head_dim"], s["n_kv_heads"] * s["head_dim"]
    one_expert = 2 * 2 * d * s["d_expert"]                  # up, down
    return {
        "mamba_projections": s["n_mamba"] * 2 * d * (in_width + d_inner),
        "scan": s["n_mamba"] * scan_flops_per_token(s),
        # q and out at the query heads' width, k and v at the KV heads'
        "projections": s["n_attention"] * 2 * d * (2 * wide + 2 * narrow),
        # q.k^T and p.v under the causal mask: on average half the sequence
        "attention": s["n_attention"] * 2 * seq_len * wide,
        "router": s["n_expert"] * 2 * d * s["router_width"],
        "shared_experts": s["n_expert"] * 2 * 2 * d * s["d_shared"],
        "held_experts": s["n_expert"] * one_expert
        * s["top_k"] * s["experts_held"] / s["router_width"],
        "head": 2 * d * s["vocab_size"],
    }


def train_flops_per_token(config: dict, seq_len: int) -> float:
    return 3.0 * sum(forward_flops_per_token(shape(config), seq_len).values())


def ssd_cost(*, tokens: int, s: dict, act_bytes: int = 2):
    """``(forward, backward)`` ``flops.KernelCost`` of one scan over
    ``tokens`` positions (whole chunks)."""
    product = tokens * scan_flops_per_token(s)
    wide = float(tokens * s["mamba_heads"] * s["mamba_head_dim"] * act_bytes)
    narrow = float(tokens * s["n_groups"] * s["d_state"] * act_bytes)
    states = float(-(-tokens // s["chunk"]) * s["mamba_heads"]
                   * s["mamba_head_dim"] * s["d_state"] * 4)
    return (flops.KernelCost(product, 2 * wide + 2 * narrow + states),
            flops.KernelCost(2 * product, 3 * wide + 4 * narrow + states))


def conv_cost(*, tokens: int, s: dict, act_bytes: int = 2):
    """``(forward, backward)`` ``flops.KernelCost`` of one depthwise
    convolution with bias and SiLU over ``tokens`` positions: bytes alone."""
    channels = (s["mamba_heads"] * s["mamba_head_dim"]
                + 2 * s["n_groups"] * s["d_state"])
    array = float(tokens * channels * act_bytes)
    return flops.KernelCost(0.0, 2 * array), flops.KernelCost(0.0, 3 * array)


def relu2_gmm_cost(*, rows: float, d_model: int, d_expert: int, n_experts: int,
                   act_bytes: int = 2, grad_bytes: int = 4) -> flops.KernelCost:
    """The grouped products of ``rows`` held rows through ``W_down
    relu(W_up x)^2``, forward and backward: six products (up, down; each
    forward, dX and dW), each product's operands and result moved once, the
    banks cast to the activation dtype."""
    product = 2.0 * rows * d_model * d_expert
    wide = float(rows * d_model * act_bytes)
    narrow = float(rows * d_expert * act_bytes)
    bank = float(n_experts * d_model * d_expert)
    forward = d_x = 2 * (wide + narrow + bank * act_bytes)
    d_w = 2 * (wide + narrow + bank * grad_bytes)
    return flops.KernelCost(flops=6 * product, hbm_bytes=forward + d_x + d_w)


def parts(config: dict, traffic: dict) -> dict:
    """``{"ssd_fwd", "ssd_bwd", "conv_fwd", "conv_bwd", "flash_fwd",
    "flash_bwd", "gmm", "xent"}`` -> ``flops.KernelCost`` of one optimizer
    step on all chips."""
    s = shape(config)
    calls = traffic["accumulation"]
    micro = traffic["micro_batch"] * math.prod(traffic["mesh"].values())
    seq_len = traffic["seq_len"]
    tokens = micro * seq_len
    ssd_f, ssd_b = ssd_cost(tokens=tokens, s=s)
    conv_f, conv_b = conv_cost(tokens=tokens, s=s)
    flash_f, flash_b = flops_afmoe.band_flash_cost(
        batch=micro, seq_len=seq_len, n_heads=s["n_heads"],
        n_kv_heads=s["n_kv_heads"], head_dim=s["head_dim"], window=None)
    gmm = relu2_gmm_cost(
        rows=flops_afmoe.held_rows(tokens, s), d_model=s["d_model"],
        d_expert=s["d_expert"], n_experts=s["experts_held"])
    xent = flops.fused_xent_cost(rows=tokens, d_model=s["d_model"],
                                 vocab_size=s["vocab_size"])
    return {
        "ssd_fwd": ssd_f * (s["n_mamba"] * calls),
        "ssd_bwd": ssd_b * (s["n_mamba"] * calls),
        "conv_fwd": conv_f * (s["n_mamba"] * calls),
        "conv_bwd": conv_b * (s["n_mamba"] * calls),
        "flash_fwd": flash_f * (s["n_attention"] * calls),
        "flash_bwd": flash_b * (s["n_attention"] * calls),
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
    does not name the scan's and the convolution's kernels."""
    from benchmark import kernel_parts
    cell = record["cell"]
    known = kernel_parts.program_kernel_names()
    if record.get("trace") is None or cell.config.get("family") != "nemotron_h" \
            or known is None or not set(
                SSD_FWD + SSD_BWD + CONV_FWD + CONV_BWD) <= set(known):
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
