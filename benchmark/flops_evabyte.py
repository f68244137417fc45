"""Operations and bytes an EvaByte stack requires, from shapes alone, beside
``benchmark/flops.py`` (whose conventions hold: a multiply-add is two
operations, only matrix products are counted, recomputation is not).

What this family adds:

* **EVA's visible pairs** (``visible_pairs``): a query at position ``i`` sees
  the ``i % window + 1`` positions of its own window up to itself and the
  ``window / chunk`` summaries of each of the ``i // window`` windows before
  it. At 16,384 positions, window 2,048, chunk 16: 16,785,408 + 7,340,032 =
  24,125,440 a head, where full causal attention sees 134,225,920. A pair
  costs ``D + D`` multiply-adds forward (the score and the value product) and
  five products backward (the scores again, dP, dV, dQ, dK): ``eva_cost``.
  Bytes, each tensor once: forward q, k, v and the two summary arrays read, o
  written; backward those with o and dO read, dq, dk, dv and the summaries'
  float32 gradients written. The pooling is elementwise work and a softmax
  over ``chunk`` numbers: not a matrix product, not counted.
* **the share of the heads**: the projections are ``d x heads_held D`` (and
  back), as the configuration's ``num_attention_heads`` holds them.
* **eight heads**: the head is ``d x (num_pred_heads x vocab_size)``, each
  head's product counted once.

Under per-layer recomputation (``assumed.remat``) a layer keeps what EVA's
forward hands its backward (``models/evabyte.py`` ``KEPT``), so each kernel
runs once a step; the counts here are of the required work, once.

``parts`` splits the Pallas calls of one optimizer step by kernel; the
readers under ``layers/`` read it.
"""

import math

from benchmark import flops

FAMILY = "evabyte"
EVA_FWD = ("eva_fwd",)
EVA_BWD = ("eva_bwd",)


def shape(config: dict) -> dict:
    """The sizes the counts need, from the configuration file."""
    return dict(
        d_model=config["hidden_size"], heads=config["num_attention_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        window=config["window_size"], chunk=config["chunk_size"],
        n_pred=config["num_pred_heads"], vocab_size=config["vocab_size"],
        n_layers=config["num_hidden_layers"])


def visible_pairs(seq_len: int, window: int, chunk: int) -> tuple:
    """``(in the window, summaries)`` (query, key) and (query, summary)
    pairs the mask keeps, of one head and one sequence of whole windows."""
    n_windows, per_window = seq_len // window, window // chunk
    own = n_windows * window * (window + 1) // 2
    earlier = window * per_window * n_windows * (n_windows - 1) // 2
    return own, earlier


def forward_flops_per_token(s: dict, seq_len: int) -> dict:
    """The whole stack, forward, per input position, by part."""
    d, width = s["d_model"], s["heads"] * s["head_dim"]
    pairs = sum(visible_pairs(seq_len, s["window"], s["chunk"]))
    return {
        "projections": s["n_layers"] * 2 * 4 * d * width,        # q, k, v, o
        # the score and the value product over the visible pairs
        "eva": s["n_layers"] * 2 * 2 * pairs * width / seq_len,
        "mlp": s["n_layers"] * 3 * 2 * d * s["d_ff"],            # gate, up, down
        "head": 2 * d * s["vocab_size"] * s["n_pred"],
    }


def train_flops_per_token(config: dict, seq_len: int) -> float:
    return 3.0 * sum(forward_flops_per_token(shape(config), seq_len).values())


def eva_cost(*, batch: int, seq_len: int, heads: int, head_dim: int,
             window: int, chunk: int, act_bytes: int = 2):
    """``(forward, backward)`` ``flops.KernelCost`` of one EVA call."""
    pairs = sum(visible_pairs(seq_len, window, chunk))
    product = 2.0 * batch * heads * pairs * head_dim     # one score-sized product
    tensor = float(batch * seq_len * heads * head_dim * act_bytes)
    summary = tensor / chunk
    return (flops.KernelCost(2 * product, 4 * tensor + 2 * summary),
            # the summaries' gradients leave the kernel float32
            flops.KernelCost(5 * product, 8 * tensor + 2 * summary
                             + 2 * summary * 4 / act_bytes))


def parts(config: dict, traffic: dict) -> dict:
    """``{"eva_fwd", "eva_bwd"}`` -> ``flops.KernelCost`` of one optimizer
    step on all chips."""
    s = shape(config)
    calls = traffic["accumulation"]
    micro = traffic["micro_batch"] * math.prod(traffic["mesh"].values())
    fwd, bwd = eva_cost(batch=micro, seq_len=traffic["seq_len"],
                        heads=s["heads"], head_dim=s["head_dim"],
                        window=s["window"], chunk=s["chunk"])
    return {"eva_fwd": fwd * (s["n_layers"] * calls),
            "eva_bwd": bwd * (s["n_layers"] * calls)}


def kernel_cost_per_step(config: dict, traffic: dict):
    cost = flops.KernelCost(0.0, 0.0)
    for part in parts(config, traffic).values():
        cost = cost + part
    return cost


def is_cell(record) -> bool:
    return record["cell"].config.get("family") == FAMILY


def cell_parts(record):
    """``parts`` of a traced run's cell, or None where there is nothing to
    read: no device trace, another family's configuration, a program that
    does not name these kernels."""
    from benchmark import kernel_parts
    cell = record["cell"]
    known = kernel_parts.program_kernel_names()
    if record.get("trace") is None or not is_cell(record) or known is None \
            or not set(EVA_FWD + EVA_BWD) <= set(known):
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
