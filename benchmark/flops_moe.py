"""Operations a routed mixture-of-experts Transformer requires, from shapes
alone, beside ``benchmark/flops.py`` (whose conventions hold: a multiply-add
is two operations, only matrix products are counted, recomputation is not).

* ``train_flops_per_token``: forward plus backward of an OLMoE-style stack
  per input position. Only the ``top_k`` experts a token is routed to are
  required; the router's product is.
* ``gmm_cost``: the grouped matmuls of the routed gated FFN for one forward
  plus backward call, as the algorithm is defined: nine products of
  rows x d_model x d_expert multiply-adds (gate, up, down; each forward, dX
  and dW), each product's operands and result moved once.
"""

import math

from benchmark import flops

GMM_KERNELS = ("moe_gmm_fwd", "moe_gmm_bwd_dx", "moe_gmm_bwd_dw")


def layer_forward_flops_per_token(*, d_model: int, d_expert: int,
                                  n_experts: int, top_k: int,
                                  seq_len: int) -> dict:
    """One block, forward, per input position, by part."""
    return {
        "projections": 2 * 4 * d_model * d_model,        # q, k, v, out
        # q.k^T and p.v under a causal mask: on average half the sequence
        "attention": 2 * seq_len * d_model,
        "router": 2 * d_model * n_experts,
        "experts": top_k * 3 * 2 * d_model * d_expert,   # gate, up, down
    }


def train_flops_per_token(*, d_model: int, n_layers: int, d_expert: int,
                          n_experts: int, top_k: int, vocab_size: int,
                          seq_len: int) -> float:
    layer = sum(layer_forward_flops_per_token(
        d_model=d_model, d_expert=d_expert, n_experts=n_experts, top_k=top_k,
        seq_len=seq_len).values())
    return 3.0 * (n_layers * layer + 2 * d_model * vocab_size)


def gmm_cost(*, rows: int, d_model: int, d_expert: int, n_experts: int,
             act_bytes: int = 2, grad_bytes: int = 4) -> flops.KernelCost:
    """``rows`` = tokens x top_k, sorted by expert. Forward: rows.gate,
    rows.up, hidden.down. dX: the three against the transposed banks. dW:
    the three transposed grouped products, written in the parameters' dtype.
    The banks reach the kernels cast to the activation dtype."""
    product = 2.0 * rows * d_model * d_expert
    wide = float(rows * d_model * act_bytes)       # a [rows, d_model] tensor
    narrow = float(rows * d_expert * act_bytes)    # a [rows, d_expert] tensor
    bank = float(n_experts * d_model * d_expert)   # elements of one bank
    forward = d_x = 3 * (wide + narrow + bank * act_bytes)
    d_w = 3 * (wide + narrow + bank * grad_bytes)
    return flops.KernelCost(flops=9 * product, hbm_bytes=forward + d_x + d_w)


def cell_gmm_cost(cell):
    """``gmm_cost`` of one optimizer step of a cell on all its chips, or None
    where the configuration has no routed experts. Mirrors
    ``families/olmoe.build``."""
    config, traffic = cell.config, cell.traffic
    if "num_experts" not in config:
        return None
    sequences = traffic["micro_batch"] * math.prod(traffic["mesh"].values())
    rows = sequences * traffic["seq_len"] * config["num_experts_per_tok"]
    return gmm_cost(rows=rows, d_model=config["hidden_size"],
                    d_expert=config["intermediate_size"],
                    n_experts=config["num_experts"]) \
        * (config["num_hidden_layers"] * traffic["accumulation"])


def gmm_seconds(record):
    """Self seconds the traced window holds under the grouped-matmul kernels'
    names, all chips; None where there is nothing to read (no device trace, a
    configuration without routed experts, a program that does not name these
    kernels). A program that names them and a trace that holds none is a
    fault, as in ``kernel_parts.roofline_pct``: the run fails."""
    from benchmark import harness, kernel_parts
    trace = record.get("trace")
    known = kernel_parts.program_kernel_names()
    if trace is None or known is None or not set(GMM_KERNELS) <= set(known) \
            or cell_gmm_cost(record["cell"]) is None:
        return None
    measured = kernel_parts.group_seconds(trace, GMM_KERNELS)
    if measured <= 0:
        found = sorted({g for d in trace.devices.values() for g in d.by_group
                        if g.startswith(kernel_parts.PREFIX)})
        raise harness.BenchmarkError(
            f"{record['cell'].name}: the program names its kernels {known}, "
            f"and the trace holds no time under {GMM_KERNELS}; Mosaic groups "
            f"in the trace: {found}")
    return measured
