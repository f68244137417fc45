"""Operations and bytes a Ling / Ring hybrid stack (``model_type``
``bailing_hybrid``: Kimi Delta Attention layers beside gated latent attention,
a group-limited sigmoid router) requires, from shapes alone, beside
``benchmark/flops.py``, ``benchmark/flops_moe.py``, ``benchmark/flops_afmoe.py``
and ``benchmark/flops_deepseek_v3.py`` (whose conventions hold: a multiply-add
is two operations, only matrix products are counted, recomputation is not, a
causal mask halves the score and value products).

What this family adds:

* **the KDA layer** is seven projections (``W_q``, ``W_k``, ``W_v``, the
  decay's ``W_f``, the output gate's ``W_g``: ``d x H 128`` each; ``W_beta``
  ``d x H``; ``W_o`` ``H 128 x d``) around three depthwise convolutions, which
  have no matrix product, and the recurrence. The recurrence is counted as the
  chunked algorithm defines it (``kda_cost``), chunk ``C``, a head ``D`` wide:
  the two triangles ``(K e^G)(K e^-G)^T`` and ``(Q e^G)(K e^-G)^T`` over the
  causal half of a chunk's pairs (``C D / 2`` multiply-adds a token each), the
  triangular solve against the chunk's ``[K e^G | V]`` **as a substitution
  needs it** (``C^2 / 2`` rows of ``2 D``: ``C D`` a token; the kernel's
  doubling executes ten ``[C, C]`` products and is not what is counted), the
  triangle against the corrected values (``C D / 2``), and three products with
  the ``[D, D]`` state: the keys and the queries against the state that enters
  and the chunk's own addition to it (``D^2`` each). Its backward is twice that
  (each product transposed twice; ``A``, the solve and ``U`` built again are
  recomputation and not counted). Bytes, each operand once: forward ``q``,
  ``k``, ``v`` read and ``o`` written at the activation's two bytes, the
  float32 log-decay read, one float32 ``[D, D]`` state a chunk and head
  written; backward ``q``, ``k``, ``v``, ``dO`` and the log-decay and the
  states read, ``dq``, ``dk``, ``dv`` and the float32 ``dg`` written. ``beta``
  (``[T, H]``) is nothing beside them and left out, so a share is never
  over-stated.
* **the three convolutions** before it (``conv_cost``): no product at all,
  ``[T, H 128]`` read and written forward, ``x`` and ``dy`` read and ``dx``
  written backward, three times a layer.
* **gated latent attention on a share of the heads**:
  ``flops_deepseek_v3.mla_flash_cost`` at the heads held; the head-wise gate
  ``d x H`` among the projections.
* **the share**, the grouped products and **the sliced head** as the other
  share configurations count them. The group-limited choice has no product.

Under per-layer recomputation (``assumed.remat``) the step runs a layer's
forward kernels twice unless the layer keeps their results
(``models/bailing_hybrid.py`` ``KEPT``: the recurrence's and flash's are
kept, the convolutions' are not); the counts here are of the required work,
once.

``parts`` splits the Pallas calls of one optimizer step by kernel group; the
readers under ``layers/`` read it.
"""

import math

from benchmark import flops, flops_afmoe, flops_deepseek_v3, flops_moe

FAMILY = "bailing_hybrid"
KDA_FWD = ("kda_fwd",)
KDA_BWD = ("kda_bwd",)
CONV_FWD = ("conv_silu_fwd",)
CONV_BWD = ("conv_silu_bwd",)
KDA, MLA = "kda", "mla"
CONVS_A_LAYER = 3                   # q, k and v, a convolution each


def shape(config: dict) -> dict:
    """The sizes the counts need, from the configuration file."""
    kinds = config["layer_types"]
    n_layers, n_dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    return dict(
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        head_dim=config["head_dim"], d_nope=config["qk_nope_head_dim"],
        d_rope=config["qk_rope_head_dim"], d_v=config["v_head_dim"],
        rank=config["kv_lora_rank"], d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["moe_shared_expert_intermediate_size"]
        * config["num_shared_experts"],
        router_width=config["router_width"],
        experts_held=config["num_experts"],
        top_k=config["num_experts_per_tok"], vocab_size=config["vocab_size"],
        chunk=config.get("assumed", {}).get("kda_chunk", 64),
        n_kda=kinds.count(KDA), n_mla=kinds.count(MLA), n_layers=n_layers,
        n_dense=n_dense, n_expert=n_layers - n_dense)


def recurrence_flops_per_token(s: dict) -> float:
    """The chunked recurrence, forward, one layer, per input position."""
    c, d = s["chunk"], s["head_dim"]
    triangles = 3 * c * d / 2       # K K^T, Q K^T, the latter against U
    solve = c * d                   # a substitution over [K e^G | V]
    state = 3 * d * d               # K S, Q S, the chunk's addition
    return 2.0 * s["n_heads"] * (triangles + solve + state)


def forward_flops_per_token(s: dict, seq_len: int) -> dict:
    """The whole stack, forward, per input position, by part."""
    d, heads = s["d_model"], s["n_heads"]
    wide = heads * s["head_dim"]
    d_qk = s["d_nope"] + s["d_rope"]
    one_expert = 3 * 2 * d * s["d_expert"]                  # gate, up, down
    return {
        "kda_projections": s["n_kda"] * 2 * (6 * d * wide + d * heads),
        "kda_recurrence": s["n_kda"] * recurrence_flops_per_token(s),
        "mla_projections": s["n_mla"] * 2 * (
            d * heads * d_qk + d * (s["rank"] + s["d_rope"])
            + s["rank"] * heads * (s["d_nope"] + s["d_v"])
            + heads * s["d_v"] * d + d * heads),
        # q.k^T at the key's width and p.v at the value's, under the causal
        # mask: on average half the sequence
        "attention": s["n_mla"] * seq_len * heads * (d_qk + s["d_v"]),
        "dense_mlp": s["n_dense"] * 3 * 2 * d * s["d_ff"],
        "router": s["n_expert"] * 2 * d * s["router_width"],
        "shared_experts": s["n_expert"] * 3 * 2 * d * s["d_shared"],
        "held_experts": s["n_expert"] * one_expert
        * s["top_k"] * s["experts_held"] / s["router_width"],
        "head": 2 * d * s["vocab_size"],
    }


def train_flops_per_token(config: dict, seq_len: int) -> float:
    return 3.0 * sum(forward_flops_per_token(shape(config), seq_len).values())


def kda_cost(*, batch: int, seq_len: int, heads: int, head_dim: int,
             chunk: int, act_bytes: int = 2):
    """``(forward, backward)`` ``flops.KernelCost`` of one call of the
    recurrence over ``batch`` sequences (whole chunks)."""
    tokens = batch * -(-seq_len // chunk) * chunk
    product = tokens * recurrence_flops_per_token(
        dict(chunk=chunk, head_dim=head_dim, n_heads=heads))
    rows = float(tokens * heads * head_dim)
    states = float(tokens // chunk * heads * head_dim * head_dim * 4)
    return (flops.KernelCost(product, rows * (4 * act_bytes + 4) + states),
            flops.KernelCost(2 * product, rows * (7 * act_bytes + 8) + states))


def conv_cost(*, tokens: int, channels: int, act_bytes: int = 2):
    """``(forward, backward)`` ``flops.KernelCost`` of one depthwise
    convolution with SiLU over ``tokens`` positions: bytes alone."""
    array = float(tokens * channels * act_bytes)
    return flops.KernelCost(0.0, 2 * array), flops.KernelCost(0.0, 3 * array)


def parts(config: dict, traffic: dict) -> dict:
    """``{"kda_fwd", "kda_bwd", "conv_fwd", "conv_bwd", "flash_fwd",
    "flash_bwd", "gmm", "xent"}`` -> ``flops.KernelCost`` of one optimizer
    step on all chips."""
    s = shape(config)
    calls = traffic["accumulation"]
    micro = traffic["micro_batch"] * math.prod(traffic["mesh"].values())
    seq_len = traffic["seq_len"]
    tokens = micro * seq_len
    kda_f, kda_b = kda_cost(batch=micro, seq_len=seq_len, heads=s["n_heads"],
                            head_dim=s["head_dim"], chunk=s["chunk"])
    conv_f, conv_b = conv_cost(tokens=tokens,
                               channels=s["n_heads"] * s["head_dim"])
    flash_f, flash_b = flops_deepseek_v3.mla_flash_cost(
        batch=micro, seq_len=seq_len, n_heads=s["n_heads"], d_nope=s["d_nope"],
        d_rope=s["d_rope"], d_v=s["d_v"])
    gmm = flops_moe.gmm_cost(
        rows=flops_afmoe.held_rows(tokens, s), d_model=s["d_model"],
        d_expert=s["d_expert"], n_experts=s["experts_held"])
    xent = flops.fused_xent_cost(rows=tokens, d_model=s["d_model"],
                                 vocab_size=s["vocab_size"])
    return {
        "kda_fwd": kda_f * (s["n_kda"] * calls),
        "kda_bwd": kda_b * (s["n_kda"] * calls),
        "conv_fwd": conv_f * (s["n_kda"] * CONVS_A_LAYER * calls),
        "conv_bwd": conv_b * (s["n_kda"] * CONVS_A_LAYER * calls),
        "flash_fwd": flash_f * (s["n_mla"] * calls),
        "flash_bwd": flash_b * (s["n_mla"] * calls),
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
    does not name the recurrence's kernels."""
    from benchmark import kernel_parts
    cell = record["cell"]
    known = kernel_parts.program_kernel_names()
    if record.get("trace") is None or cell.config.get("family") != FAMILY \
            or known is None or not set(KDA_FWD + KDA_BWD) <= set(known):
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
