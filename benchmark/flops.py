"""Operations the algorithm requires, from shapes alone.

Two counts live here and they are not the same thing:

* ``train_flops_per_token``: what the forward and backward passes of a
  Transformer *require* per input position, the numerator of model FLOP/s
  utilization. Recomputation is not counted, a causal mask halves the score
  and value products, and a head that only predicts some positions is
  counted on those positions only.
* ``flash_attention_cost`` / ``fused_xent_cost``: operations and HBM bytes
  of one forward plus backward call of a kernel *as its algorithm is
  defined* (flash attention recomputes the score tile in its backward pass,
  the fused head recomputes the logits), the numerator of a kernel's
  roofline share.

A multiply-add is two operations. Only matrix products are counted:
LayerNorm, GELU, softmax, biases, residuals and the embedding gather are a
fraction of a percent of the products at these widths and are left out,
which makes every utilization here a slight under-estimate, never an
over-estimate.
"""

import dataclasses


def layer_forward_flops_per_token(d_model: int, d_ff: int, seq_len: int,
                                  causal: bool) -> float:
    """One Transformer block, forward, per input position."""
    projections = 2 * 4 * d_model * d_model          # q, k, v, out
    mlp = 2 * 2 * d_model * d_ff                     # in, out
    # q.k^T and p.v: seq_len x d_model multiply-adds each per position;
    # under a causal mask a position sees on average half the sequence.
    attention = 2 * 2 * seq_len * d_model
    if causal:
        attention //= 2
    return float(projections + mlp + attention)


def train_flops_per_token(*, d_model: int, n_layers: int, d_ff: int,
                          vocab_size: int, seq_len: int, causal: bool,
                          predicted_fraction: float = 1.0) -> float:
    """Forward plus backward (twice the forward: one product for the
    activations' gradient, one for the weights') per input position."""
    layers = n_layers * layer_forward_flops_per_token(d_model, d_ff, seq_len,
                                                      causal)
    head = 2 * d_model * vocab_size * predicted_fraction
    return 3.0 * (layers + head)


@dataclasses.dataclass(frozen=True)
class KernelCost:
    """One forward+backward call of a kernel."""
    flops: float
    hbm_bytes: float

    def least_seconds(self, peaks) -> float:
        return max(self.flops / peaks.bf16_flops_per_s,
                   self.hbm_bytes / peaks.hbm_bytes_per_s)

    def bound(self, peaks) -> str:
        return ("compute" if self.flops / peaks.bf16_flops_per_s
                >= self.hbm_bytes / peaks.hbm_bytes_per_s else "memory")

    def __add__(self, other: "KernelCost") -> "KernelCost":
        return KernelCost(self.flops + other.flops,
                          self.hbm_bytes + other.hbm_bytes)

    def __mul__(self, n: float) -> "KernelCost":
        return KernelCost(self.flops * n, self.hbm_bytes * n)


def flash_attention_cost(*, batch: int, seq_len: int, n_heads: int,
                         head_dim: int, causal: bool,
                         act_bytes: int = 2) -> KernelCost:
    """Flash attention, forward and backward, [batch, seq, heads, head_dim].

    Forward: q.k^T and p.v. Backward: the score tile again, dP = dO.v^T,
    dV = p^T.dO, dQ = dS.k, dK = dS^T.q: seven products of
    seq x seq x head_dim multiply-adds per head, halved under a causal mask.
    HBM: the forward reads q, k, v and writes o; the backward reads q, k, v,
    o, dO and writes dq, dk, dv (the per-row log-sum-exp is 1/head_dim of
    that and left out)."""
    product = 2.0 * batch * n_heads * seq_len * seq_len * head_dim
    if causal:
        product /= 2
    tensor = float(batch * seq_len * n_heads * head_dim * act_bytes)
    return KernelCost(flops=7 * product, hbm_bytes=(4 + 8) * tensor)


def fused_xent_cost(*, rows: int, d_model: int, vocab_size: int,
                    act_bytes: int = 2, table_bytes: int = 4) -> KernelCost:
    """Fused head + softmax cross-entropy, forward and backward.

    Forward: rows x d_model x vocab logits. Backward: the logits again, then
    dh = dlogits.W and dW = h^T.dlogits: four products. HBM: h and the table
    read in both passes, dh and dW written (per-row scalars left out)."""
    product = 2.0 * rows * d_model * vocab_size
    h = float(rows * d_model * act_bytes)
    table = float(vocab_size * d_model * table_bytes)
    return KernelCost(flops=4 * product, hbm_bytes=3 * h + 3 * table)
