"""Operations an AFMoE (Trinity) stack requires, from shapes alone, beside
``benchmark/flops.py`` and ``benchmark/flops_moe.py`` (whose conventions
hold: a multiply-add is two operations, only matrix products are counted,
recomputation is not).

What differs from a uniform stack and is counted as it is, never as the
causal triangle or the whole bank (an over-count reads as an impossible
share of a roofline):

* **the band.** A sliding layer's query at ``i`` sees ``min(i + 1, window)``
  keys, a full layer's ``i + 1``: ``band_pairs`` is their sum over the
  sequence, the (query, key) pairs a score-sized product runs over.
* **grouped KV heads.** The score and value products run once a *query* head;
  K, V, dK and dV are tensors of the KV heads and move once each.
* **the share.** Of the router's ``top_k`` choices a token the experts held
  here receive ``top_k x held / width`` on average; the shared expert and the
  router's product every token.
* **the sliced head**: the rows of the vocabulary held here.

``parts`` splits the Pallas calls of one optimizer step by kernel group, as
``benchmark/kernel_parts.py`` does for GPT-2; the new readers under
``layers/`` read it.
"""

import math

from benchmark import flops, flops_moe

FLASH_FWD = ("flash_fwd",)
FLASH_BWD = ("flash_bwd_dkv", "flash_bwd_dq")
SLIDING = "sliding_attention"


def band_pairs(seq_len: int, window) -> int:
    """(query, key) pairs of one head over one sequence: ``sum_i min(i + 1,
    window)``; ``window=None`` is the causal triangle."""
    w = seq_len if window is None else min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def shape(config: dict) -> dict:
    """The sizes the counts need, from the configuration file."""
    kinds = config["layer_types"]
    return dict(
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        d_ff=config["intermediate_size"], d_expert=config["moe_intermediate_size"],
        router_width=config["router_width"], experts_held=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        n_shared=config["num_shared_experts"], vocab_size=config["vocab_size"],
        window=config["sliding_window"], n_dense=config["num_dense_layers"],
        n_sliding=sum(k == SLIDING for k in kinds),
        n_full=sum(k != SLIDING for k in kinds))


def forward_flops_per_token(s: dict, seq_len: int) -> dict:
    """The whole stack, forward, per input position, by part."""
    wide, narrow = s["n_heads"] * s["head_dim"], s["n_kv_heads"] * s["head_dim"]
    n_layers = s["n_sliding"] + s["n_full"]
    n_expert_layers = n_layers - s["n_dense"]
    pairs = (s["n_sliding"] * band_pairs(seq_len, s["window"])
             + s["n_full"] * band_pairs(seq_len, None))
    one_expert = 3 * 2 * s["d_model"] * s["d_expert"]      # gate, up, down
    return {
        # q, gate and out at the query heads' width, k and v at the KV heads'
        "projections": n_layers * 2 * s["d_model"] * (3 * wide + 2 * narrow),
        # q.k^T and p.v over the band, every query head
        "attention": 2 * 2 * wide * pairs / seq_len,
        "dense_mlp": s["n_dense"] * 3 * 2 * s["d_model"] * s["d_ff"],
        "router": n_expert_layers * 2 * s["d_model"] * s["router_width"],
        "shared_experts": n_expert_layers * s["n_shared"] * one_expert,
        "held_experts": n_expert_layers * one_expert
        * s["top_k"] * s["experts_held"] / s["router_width"],
        "head": 2 * s["d_model"] * s["vocab_size"],
    }


def train_flops_per_token(config: dict, seq_len: int) -> float:
    return 3.0 * sum(forward_flops_per_token(shape(config), seq_len).values())


def band_flash_cost(*, batch: int, seq_len: int, n_heads: int, n_kv_heads: int,
                    head_dim: int, window, act_bytes: int = 2):
    """``(forward, backward)`` ``flops.KernelCost`` of one flash call under
    the band: the forward two score-sized products over the band's pairs,
    the backward five; the forward reads q, k, v and writes o, the backward
    reads q, k, v, o, dO and writes dq, dk, dv, with k, v, dk, dv at the KV
    heads (counted once a KV head, however many query heads read them)."""
    product = 2.0 * batch * n_heads * band_pairs(seq_len, window) * head_dim
    wide = float(batch * seq_len * n_heads * head_dim * act_bytes)
    narrow = float(batch * seq_len * n_kv_heads * head_dim * act_bytes)
    return (flops.KernelCost(2 * product, 2 * wide + 2 * narrow),
            flops.KernelCost(5 * product, 4 * wide + 4 * narrow))


def held_rows(tokens: int, s: dict) -> float:
    """Rows the held experts receive from ``tokens`` tokens, on average."""
    return tokens * s["top_k"] * s["experts_held"] / s["router_width"]


def parts(config: dict, traffic: dict) -> dict:
    """``{"flash_fwd", "flash_bwd", "gmm", "xent"}`` -> ``flops.KernelCost``
    of one optimizer step on all chips."""
    s = shape(config)
    calls = traffic["accumulation"]
    micro = traffic["micro_batch"] * math.prod(traffic["mesh"].values())
    seq_len = traffic["seq_len"]
    common = dict(batch=micro, seq_len=seq_len, n_heads=s["n_heads"],
                  n_kv_heads=s["n_kv_heads"], head_dim=s["head_dim"])
    slide_f, slide_b = band_flash_cost(window=s["window"], **common)
    full_f, full_b = band_flash_cost(window=None, **common)
    n_expert_layers = s["n_sliding"] + s["n_full"] - s["n_dense"]
    gmm = flops_moe.gmm_cost(
        rows=held_rows(micro * seq_len, s), d_model=s["d_model"],
        d_expert=s["d_expert"], n_experts=s["experts_held"])
    xent = flops.fused_xent_cost(rows=micro * seq_len, d_model=s["d_model"],
                                 vocab_size=s["vocab_size"])
    return {
        "flash_fwd": (slide_f * s["n_sliding"] + full_f * s["n_full"]) * calls,
        "flash_bwd": (slide_b * s["n_sliding"] + full_b * s["n_full"]) * calls,
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
    does not name its kernels."""
    from benchmark import kernel_parts
    cell = record["cell"]
    if record.get("trace") is None or cell.config.get("family") != "afmoe" \
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
