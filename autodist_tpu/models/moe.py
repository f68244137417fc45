"""Mixture-of-Experts Transformer LM — the expert-parallel workload.

The reference has no MoE or expert parallelism (its strategy nodes are variables
only, ``strategy.proto:36-42``); this extends the framework beyond reference parity
using the mesh's ``expert`` axis. The design is the standard TPU MoE formulation
(GShard/Switch): routing is expressed as dense einsums against one-hot dispatch and
combine tensors with a **static capacity** per expert, and expert FFN weights carry
a leading expert dimension sharded ``P("expert", ...)``. Under ``jit`` the XLA SPMD
partitioner turns the dispatch/return einsums into ``all_to_all``s over the expert
axis — no manual collectives, and the per-expert matmuls stay MXU-shaped batched
GEMMs.

Top-1 (Switch) routing keeps shapes static: tokens beyond an expert's capacity are
dropped (their combine weight is zero, so they pass through the residual only), the
standard TPU-friendly trade.

Beside it, the dropless top-k path (:func:`topk_route`, :func:`routed_experts`,
:class:`RoutedFFN`; ``models/olmoe.py`` stacks it): every token x slot row is
sorted by expert into ragged groups and the experts run as grouped matmuls
(``ops/grouped_matmul.py``) over exactly those rows. Shapes stay static because the
row count is (tokens x k); only the group boundaries are run-time data. No
``[tokens, experts, capacity]`` tensor exists and no token is dropped or padded.

Last, what the sigmoid-routed families (``models/afmoe.py``,
``models/lfm2_moe.py``, ``models/nemotron_h.py``, ``models/deepseek_v3.py``)
share and none copies: :class:`GatedMLP` and :class:`PlainMLP`, the expert's
form as an argument (:data:`EXPERT_FORMS`: gated-SiLU over three banks,
``relu2`` over two), :class:`RoutedShare` (the expert layer's module: one
chip's share of the routed experts with the ``expert_bias`` leaf and its
zero-valued loss term, beside an optional shared expert), :func:`check_share`
(the ranges a share's config holds to), :func:`balanced_optimizer` (the
aux-loss-free balancing rule as an optax transformation) and
:func:`balance_expert_bias` (the same rule alone, before training). The
stack around the layers is ``models/decoder.py``'s.
"""

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from autodist_tpu.models.transformer_lm import (MultiHeadAttention,
                                                TransformerLMConfig, causal_mask)

# ``checkpoint_name``s a caller's ``jax.checkpoint`` may list in its policy
# (``models/nemotron_h.py`` and ``models/deepseek_v3.py`` ``KEPT``); the
# identity, lowered to nothing, outside one: :class:`PlainMLP`'s ``up``
# product, :class:`GatedMLP`'s ``gate`` and ``up``, the router's logits in
# :class:`RoutedShare` (six bfloat16 passes to make again), and what
# pass 0 of :func:`_held_passes` makes for its transpose.
KEPT_UP = "mlp_up"
KEPT_GATE = "mlp_gate"
KEPT_ROUTER_LOGITS = "router_logits"
KEPT_PASS = "held_pass_0"


@dataclasses.dataclass(frozen=True)
class MoETransformerLMConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 6
    d_ff: int = 2048
    max_len: int = 1024
    n_experts: int = 8
    capacity_factor: float = 1.25   # capacity = ceil(tokens/expert * factor)
    router_aux_weight: float = 1e-2  # Switch load-balancing loss weight
    dtype: Any = jnp.bfloat16
    # Fused pallas head+loss (ops/fused_xent): logits never materialize in HBM;
    # same win as the flagship (transformer_lm.fused_head).
    fused_head: bool = False

    def __post_init__(self):
        if self.d_model % self.n_heads:
            raise ValueError("d_model must be divisible by n_heads")
        if self.n_experts < 2:
            raise ValueError("n_experts must be >= 2")

    def attn_config(self) -> TransformerLMConfig:
        """The dense attention sub-config reused from the dense LM."""
        return TransformerLMConfig(
            vocab_size=self.vocab_size, d_model=self.d_model, n_heads=self.n_heads,
            n_layers=self.n_layers, d_ff=self.d_ff, max_len=self.max_len,
            dtype=self.dtype, tied_output=False)


def switch_route(logits: jax.Array, capacity: int
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-1 routing with static capacity.

    logits: [B, S, E] router scores. Returns (dispatch [B, S, E, C] one-hot,
    combine [B, S, E, C] = dispatch * router probability, aux_loss scalar).
    All shapes static; overflow tokens get all-zero dispatch rows.
    """
    n_experts = logits.shape[-1]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)                       # [B, S]
    assignment = jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.float32)

    # Position of each token within its expert's queue, in sequence order.
    position = jnp.cumsum(assignment, axis=1) * assignment - 1.0   # [B, S, E]
    in_capacity = (position >= 0) & (position < capacity)
    dispatch = jnp.einsum(
        "bse,bsec->bsec", assignment * in_capacity,
        jax.nn.one_hot(jnp.clip(position, 0, capacity - 1).astype(jnp.int32),
                       capacity, dtype=jnp.float32))

    top_prob = jnp.max(probs, axis=-1)                             # [B, S]
    combine = dispatch * top_prob[..., None, None]

    # Switch aux loss: E * mean_e(fraction routed to e * mean router prob for e).
    frac_routed = assignment.mean(axis=(0, 1))                     # [E]
    mean_prob = probs.mean(axis=(0, 1))                            # [E]
    aux = n_experts * jnp.sum(frac_routed * mean_prob)
    return dispatch, combine, aux


class MoEFFN(nn.Module):
    """Expert-parallel FFN: route -> all_to_all (implicit) -> batched GEMM -> return."""

    config: MoETransformerLMConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        b, s, m = x.shape
        capacity = int(np.ceil(s * cfg.capacity_factor / cfg.n_experts)) or 1

        router = nn.Dense(cfg.n_experts, use_bias=False, dtype=jnp.float32,
                          param_dtype=jnp.float32, name="router")
        # Expert weights: leading expert dim — the plan shards it P("expert",..).
        w_in = self.param("experts_in", nn.initializers.lecun_normal(),
                          (cfg.n_experts, m, cfg.d_ff), jnp.float32)
        w_out = self.param("experts_out", nn.initializers.lecun_normal(),
                           (cfg.n_experts, cfg.d_ff, m), jnp.float32)

        dispatch, combine, aux = switch_route(router(x), capacity)
        dispatch = dispatch.astype(cfg.dtype)
        combine = combine.astype(cfg.dtype)

        # Dispatch einsum: XLA inserts the token all_to_all (data <-> expert axes).
        expert_in = jnp.einsum("bsec,bsm->ebcm", dispatch, x)
        h = jnp.einsum("ebcm,emf->ebcf", expert_in, w_in.astype(cfg.dtype))
        h = nn.gelu(h)
        expert_out = jnp.einsum("ebcf,efm->ebcm", h, w_out.astype(cfg.dtype))
        y = jnp.einsum("bsec,ebcm->bsm", combine, expert_out)
        return y, aux


class MoEBlock(nn.Module):
    config: MoETransformerLMConfig

    @nn.compact
    def __call__(self, x, mask):
        cfg = self.config
        attn_cfg = cfg.attn_config()
        h = nn.LayerNorm(dtype=cfg.dtype, name="ln_attn")(x)
        x = x + MultiHeadAttention(attn_cfg, name="attn")(h, mask)
        h = nn.LayerNorm(dtype=cfg.dtype, name="ln_moe")(x)
        y, aux = MoEFFN(cfg, name="moe")(h)
        return x + y, aux


class MoETransformerLM(nn.Module):
    """Decoder-only LM with an MoE FFN in every block. Returns (logits, aux_loss)."""

    config: MoETransformerLMConfig

    @nn.compact
    def __call__(self, tokens, return_hidden=False):
        cfg = self.config
        _, length = tokens.shape
        emb = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                       param_dtype=jnp.float32, name="embed")
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (cfg.max_len, cfg.d_model), jnp.float32)
        x = emb(tokens) + pos[None, :length, :].astype(cfg.dtype)
        mask = causal_mask(length, cfg.dtype)

        aux_total = jnp.zeros((), jnp.float32)
        for i in range(cfg.n_layers):
            x, aux = MoEBlock(cfg, name=f"block_{i}")(x, mask)
            aux_total = aux_total + aux

        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_f")(x)
        if return_hidden:
            # The fused-head loss owns the projection; head params exist from
            # init (which runs the normal path below).
            return x, aux_total / cfg.n_layers
        # Head matmul in compute dtype (the loss upcasts for the softmax) — an
        # f32 vocab projection runs at a fraction of the bf16 MXU rate.
        logits = nn.Dense(cfg.vocab_size, dtype=cfg.dtype, param_dtype=jnp.float32,
                          use_bias=False, name="lm_head")(x)
        return logits, aux_total / cfg.n_layers


def make_loss_fn(model: MoETransformerLM) -> Callable:
    """Next-token cross entropy + router load-balancing aux loss."""
    cfg = model.config

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        if cfg.fused_head:
            from autodist_tpu.models.common import fused_lm_head_nll
            h, aux = model.apply({"params": params}, inputs, return_hidden=True)
            nll = fused_lm_head_nll(h, params, targets)
            return nll.mean() + cfg.router_aux_weight * aux
        logits, aux = model.apply({"params": params}, inputs)
        logprobs = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logprobs, targets[..., None], axis=-1)[..., 0]
        return nll.mean() + cfg.router_aux_weight * aux

    return loss_fn


def init_params(config: MoETransformerLMConfig, rng: Optional[jax.Array] = None,
                batch_size: int = 2):
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    model = MoETransformerLM(config)
    tokens = jnp.zeros((batch_size, min(8, config.max_len)), jnp.int32)
    from autodist_tpu.models.common import jit_init
    return model, jit_init(model, tokens, rng=rng)


def synthetic_batch(config: MoETransformerLMConfig, batch_size: int, seq_len: int,
                    seed: int = 0):
    rng = np.random.RandomState(seed)
    return {"tokens": rng.randint(0, config.vocab_size,
                                  size=(batch_size, seq_len + 1)).astype(np.int32)}


# ----------------------------------------------------- dropless top-k routing

class Route(NamedTuple):
    """Where every (token, slot) row goes. ``T`` tokens, ``k`` slots each."""
    indices: jax.Array       # [T, k] int32 expert of each slot, best first
    weights: jax.Array       # [T, k] the router's probabilities as they are
    group_sizes: jax.Array   # [E] int32 rows each expert receives; sums to T*k
    perm: jax.Array          # [T*k] sorted row i holds flat slot perm[i]
    inv_perm: jax.Array      # [T*k] flat slot j sits at sorted row inv_perm[j]


def _sorted_route(indices, weights, router_width: int, first_expert: int = 0,
                  n_held: Optional[int] = None) -> Route:
    """The stable sort of the ``T*k`` (token, slot) rows by expert, which
    every router shares. Where the layer holds only the experts
    ``[first_expert, first_expert + n_held)`` of the ``router_width`` the
    router chooses among (one chip's share under expert parallelism), rows of
    the held experts sort first, by local expert, and every row of an absent
    expert after them: ``group_sizes`` is ``[n_held]`` and sums to the held
    rows, which are the first of ``perm``."""
    n_slots = indices.size
    n_held = router_width if n_held is None else n_held
    flat = indices.reshape(-1).astype(jnp.int32)
    if n_held != router_width:
        local = flat - first_expert
        flat = jnp.where((local >= 0) & (local < n_held), local, n_held)
    rows = jnp.arange(n_slots, dtype=jnp.int32)
    # the sort's own first output is ``flat[perm]``: no gather by ``perm``
    by_expert, perm = jax.lax.sort((flat, rows), num_keys=1, is_stable=True)
    # and the inverse is the rows sorted by ``perm``: no scatter by it
    _, inv_perm = jax.lax.sort((perm, rows), num_keys=1)
    ends = jnp.searchsorted(by_expert, jnp.arange(n_held, dtype=jnp.int32),
                            side="right").astype(jnp.int32)
    group_sizes = jnp.diff(ends, prepend=0)
    return Route(indices.astype(jnp.int32), weights, group_sizes, perm,
                 inv_perm)


def _chosen(scores, indices):
    """``scores[t, indices[t, j]]``, ``[T, E]`` by ``[T, k]``, as a select
    against the router's width and a sum over it (one term is not zero, so
    the sum is that term to the bit). ``take_along_axis`` is a gather of
    ``T*k`` scalars by index and its transpose a scatter-add of as many,
    7-10 ns a scalar on the chip; this and its transpose (the same select,
    summed over ``k``) are one elementwise pass each."""
    hot = indices[..., None] == jnp.arange(scores.shape[-1], dtype=indices.dtype)
    return jnp.where(hot, scores[:, None, :], 0).sum(axis=-1)


def topk_route(probs: jax.Array, k: int, bias=None, *, first_expert: int = 0,
               n_held: Optional[int] = None) -> Route:
    """The ``k`` largest router probabilities of each token and the sort of the
    ``T*k`` (token, slot) rows by expert. Dropless: every row lands in its
    expert's group (``group_sizes`` sums to ``T*k`` where every expert is
    held), the weights are the softmax values themselves (not renormalised over
    the chosen), and the sort is stable, so a group holds its rows in token
    order. This router has no ``bias``."""
    if bias is not None:
        raise ValueError("topk_route takes no bias")
    # ``top_k``'s own values would do, but their transpose is a scatter-add
    # of ``T*k`` scalars by index
    _, indices = jax.lax.top_k(probs, k)
    return _sorted_route(indices, _chosen(probs, indices), probs.shape[1],
                         first_expert, n_held)


def group_limited(choice: jax.Array, n_group: int, topk_group: int):
    """``choice [T, E]`` with the experts outside each token's ``topk_group``
    best groups at ``-inf``: the experts lie in ``n_group`` equal groups in
    their order, a group's score is the sum of its two largest entries, and
    the ``topk_group`` groups that score highest stay (DeepSeek-V3's
    ``noaux_tc``; ties go to the lower group, as ``top_k``'s do). The groups
    kept are marked by a select against the ``n_group`` group numbers, not
    gathered or scattered by index (gauges ``moe.route.groups``,
    ``moe.route.groups_kept``)."""
    from autodist_tpu import telemetry
    tokens, width = choice.shape
    if width % n_group or not 1 <= topk_group <= n_group:
        raise ValueError(f"{width} experts are not {n_group} equal groups of "
                         f"which {topk_group} stay")
    telemetry.gauge("moe.route.groups").set(n_group)
    telemetry.gauge("moe.route.groups_kept").set(topk_group)
    grouped = choice.reshape(tokens, n_group, width // n_group)
    best_two, _ = jax.lax.top_k(grouped, min(2, width // n_group))
    _, kept = jax.lax.top_k(best_two.sum(axis=-1), topk_group)     # [T, kept]
    stays = (kept[..., None] == jnp.arange(n_group, dtype=kept.dtype)
             ).any(axis=1)                                         # [T, n_group]
    return jnp.where(stays[..., None], grouped, -jnp.inf).reshape(tokens, width)


def sigmoid_topk_route(scores: jax.Array, k: int, bias=None, *,
                       route_norm: bool = True, route_scale: float = 1.0,
                       route_eps: float = 1e-20, first_expert: int = 0,
                       n_held: Optional[int] = None, n_group: int = 1,
                       topk_group: int = 1) -> Route:
    """The router of the sigmoid-scored mixtures: ``scores = sigmoid(h.Wr)``
    in float32, the ``k`` experts with the largest ``scores + bias`` are
    chosen (under ``n_group > 1`` among the ``topk_group`` best groups only:
    :func:`group_limited`), and their weights are the scores themselves,
    without the bias (which steers the load and takes no gradient), divided
    by their sum over the chosen (+ ``route_eps``: AFMoE's 1e-20, LFM2's
    1e-6) under ``route_norm`` and multiplied by ``route_scale``. The sort is
    :func:`topk_route`'s."""
    choice = scores if bias is None else scores + jax.lax.stop_gradient(bias)
    if n_group > 1:
        choice = group_limited(jax.lax.stop_gradient(choice), n_group,
                               topk_group)
    _, indices = jax.lax.top_k(choice, k)
    weights = _chosen(scores, indices)
    if route_norm:
        # made before their sum over ``k`` is begun: folded into the select's
        # sum over the width, one reduction over both, the chip adds the
        # ``k`` terms up in another order than ``take_along_axis``'s did (a
        # last bit of the weights) and takes five times as long over it
        weights = jax.lax.optimization_barrier(weights)
        weights = weights / (weights.sum(axis=-1, keepdims=True) + route_eps)
    return _sorted_route(indices, weights * route_scale, scores.shape[1],
                         first_expert, n_held)


@jax.custom_vjp
def _permute_rows(x, perm, inv_perm):
    """``out[i] = x[perm[i]]`` for a permutation: its transpose is the gather
    by the inverse, not the scatter autodiff would write."""
    return jnp.take(x, perm, axis=0)


def _permute_rows_fwd(x, perm, inv_perm):
    return jnp.take(x, perm, axis=0), (inv_perm,)


def _permute_rows_bwd(residuals, g):
    (inv_perm,) = residuals
    return jnp.take(g, inv_perm, axis=0), None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch_rows(x, perm, inv_perm, k):
    """``[T, d] -> [T*k, d]``: sorted row ``i`` is token ``perm[i] // k``. The
    transpose un-sorts by the inverse and sums a token's ``k`` rows: two
    gathers and a reduction where autodiff would scatter-add."""
    return jnp.take(x, perm // k, axis=0)


def _dispatch_rows_fwd(x, perm, inv_perm, k):
    return jnp.take(x, perm // k, axis=0), (inv_perm,)


def _dispatch_rows_bwd(k, residuals, g):
    (inv_perm,) = residuals
    unsorted = jnp.take(g, inv_perm, axis=0)
    dx = unsorted.reshape(-1, k, g.shape[-1]).astype(jnp.float32).sum(axis=1)
    return dx.astype(g.dtype), None, None


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


def _rows_at(x, token):
    """``x[token]`` for tokens that are in bounds by construction (a slot
    index over ``top_k``): ``jnp.take`` would pass over the result once more
    to fill what an index out of bounds names."""
    return x.at[token].get(mode="promise_in_bounds")


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _take_rows(x, token, count, plan, n_tokens):
    """``[T, d] -> [R, d]``: compacted row ``i`` is token ``token[i]``,
    XLA's gather (at this chip's bandwidth, rows not held included: PERF.md
    §6, PR 34). The transpose adds a token's held rows ``i < count`` up in
    float32 (autodiff would add them in the rows' dtype) in the kernel of
    ``ops/moe_rows.py``, where XLA would scatter-add every row under repeated
    indices; ``plan`` is the walk it shares with the combine."""
    return _rows_at(x, token)


def _take_rows_fwd(x, token, count, plan, n_tokens):
    return _rows_at(x, token), (token, count, plan)


def _take_rows_bwd(n_tokens, residuals, g):
    from autodist_tpu.ops.moe_rows import moe_rows_combine
    token, count, plan = residuals
    dx = moe_rows_combine(g, None, token, count, n_tokens, plan, dtype=g.dtype)
    return dx, None, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _add_rows(out, weight, token, count, plan, n_tokens):
    """``[R, d] -> [T, d]`` float32: token ``t`` is the sum of ``weight[r] *
    out[r]`` over its held rows ``r < count``, added in float32 in row order
    by a kernel that fetches only those rows (``ops/moe_rows.py``) where XLA
    would scatter-add all ``R`` under repeated indices. The transpose is
    XLA's gather of the cotangent by ``token``, scaled for ``out`` and dotted
    with ``out`` for ``weight`` (zero past ``count``, where ``weight`` is)."""
    from autodist_tpu.ops.moe_rows import moe_rows_combine
    return moe_rows_combine(out, weight, token, count, n_tokens, plan)


def _add_rows_fwd(out, weight, token, count, plan, n_tokens):
    return (_add_rows(out, weight, token, count, plan, n_tokens),
            (out, weight, token))


def _add_rows_bwd(n_tokens, residuals, g):
    out, weight, token = residuals
    taken = _rows_at(g, token)
    d_weight = jnp.sum(taken * out.astype(jnp.float32), axis=-1)
    return ((weight[:, None] * taken).astype(out.dtype),
            d_weight.astype(weight.dtype), None, None, None)


_add_rows.defvjp(_add_rows_fwd, _add_rows_bwd)


# What one expert computes, by name: ``W_down(silu(W_gate h) * W_up h)`` over
# three banks, or ``W_down relu(W_up h)^2`` over two (no ``gate``: None).
EXPERT_FORMS = ("gated_silu", "relu2")


def relu2(x):
    return jnp.square(nn.relu(x))


def _expert_mlps(rows, gate, up, down, group_sizes, form="gated_silu"):
    from autodist_tpu.ops.grouped_matmul import gmm
    if form not in EXPERT_FORMS or (gate is None) != (form == "relu2"):
        raise ValueError(f"expert form {form!r} (valid: {EXPERT_FORMS}) with "
                         f"{'no' if gate is None else 'a'} gate bank")
    with jax.named_scope("moe.experts"):
        hidden = gmm(rows, up, group_sizes)
        if form == "relu2":
            hidden = relu2(hidden)
        else:
            hidden = nn.silu(gmm(rows, gate, group_sizes)) * hidden
        return gmm(hidden, down, group_sizes)


def _held_pass(c, x, weights, gate, up, down, perm, offsets, top_k: int,
               bound: int, form: str = "gated_silu"):
    """Pass ``c`` over the held rows: the part of the result that the sorted
    rows ``[c * bound, (c + 1) * bound)`` give, ``[T, d]`` float32. ``perm``
    holds the held rows' flat slots first, by expert; ``offsets [H + 1]`` the
    first sorted row of each held expert and the end of the last. Of the
    ``bound`` rows the first ``count`` are held: the combine and the
    dispatch's transpose (``ops/moe_rows.py``) move those and no other."""
    from autodist_tpu.ops.moe_rows import combine_plan
    n_tokens = x.shape[0]
    first = c * bound
    kept = jax.lax.dynamic_slice(perm, (first,), (bound,))
    token = kept // top_k
    sizes = jnp.diff(jnp.clip(offsets, first, first + bound))
    count = jnp.clip(offsets[-1] - first, 0, bound)
    weight = jnp.where(jnp.arange(bound) < count, jnp.take(weights, kept), 0.0)
    with jax.named_scope("moe.route"):
        plan = combine_plan(token, count, n_tokens)
    with jax.named_scope("moe.dispatch"):
        rows = _take_rows(x, token, count, plan, n_tokens)
    out = _expert_mlps(rows, gate, up, down, sizes, form)
    with jax.named_scope("moe.combine"):
        return _add_rows(out, weight, token, count, plan, n_tokens)


# One trace of the pass for every layer and loop of a program that share its
# shapes (the pass number an argument, not a constant): each call of the plain
# function traces its three kernels again, and a share calls it three times a
# layer (pass 0, the forward's loop, the transpose's loop).
_traced_once_pass = jax.jit(_held_pass,
                            static_argnames=("top_k", "bound", "form"))


def _pass_of(perm, offsets, top_k: int, bound: int, form: str):
    """``(c, x, weights, gate, up, down) -> pass c``, of one routing."""
    return functools.partial(_traced_once_pass, perm=perm, offsets=offsets,
                             top_k=top_k, bound=bound, form=form)


def _passes(held_rows, bound: int):
    return (held_rows + bound - 1) // bound


def _later_passes(run, y, operands, passes):
    return jax.lax.fori_loop(1, passes, lambda c, y: y + run(c, *operands), y)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _held_passes(x, weights, gate, up, down, perm, offsets, top_k, bound,
                 form="gated_silu"):
    """Every pass the held rows need, ``ceil(held rows / bound)`` of them, a
    number known only at run time: pass 0, then a loop over :func:`_held_pass`
    from 1 whose buffers are one pass's.

    Pass 0 runs once and keeps what its transpose reads (the gathered rows,
    the three products, the weights and indices); the transpose starts its
    float32 totals from pass 0's. The bound is twice the mean held rows, so
    pass 0 is nearly always all there is, and a forward run again for the
    transpose would be paid by every step. The passes past it keep nothing (a
    loop of unknown length has no transpose of its own, and no buffer of
    unknown size to keep things in): the transpose runs as many again, each
    recomputed and transposed in turn."""
    run = _pass_of(perm, offsets, top_k, bound, form)
    operands = (x, weights, gate, up, down)
    return _later_passes(run, run(0, *operands), operands,
                         _passes(offsets[-1], bound))


def _held_passes_fwd(x, weights, gate, up, down, perm, offsets, top_k, bound,
                     form):
    run = _pass_of(perm, offsets, top_k, bound, form)
    operands = (x, weights, gate, up, down)
    y, transpose_first = jax.vjp(functools.partial(run, 0), *operands)
    # what pass 0 made for its transpose (not what it was given), by name
    given = {id(t) for t in operands}
    transpose_first = jax.tree_util.tree_map(
        lambda t: t if id(t) in given else checkpoint_name(t, KEPT_PASS),
        transpose_first)
    y = _later_passes(run, y, operands, _passes(offsets[-1], bound))
    return y, (transpose_first, operands, perm, offsets)


def _held_passes_bwd(top_k, bound, form, residuals, g):
    transpose_first, operands, perm, offsets = residuals
    run = _pass_of(perm, offsets, top_k, bound, form)

    def one(c, total):
        _, transpose = jax.vjp(functools.partial(run, c), *operands)
        return jax.tree_util.tree_map(
            lambda t, part: t + part.astype(t.dtype), total, transpose(g))

    # a token's rows add up over the passes in float32, as within one
    # (a form without a gate bank has None in its place, here as there)
    first = jax.tree_util.tree_map(lambda part: part.astype(jnp.float32),
                                   transpose_first(g))
    total = jax.lax.fori_loop(1, _passes(offsets[-1], bound), one, first)
    return (*jax.tree_util.tree_map(lambda t, a: t.astype(a.dtype), total,
                                    operands), None, None)


_held_passes.defvjp(_held_passes_fwd, _held_passes_bwd)


def routed_experts(x, scores, gate, up, down, bias=None, *, top_k: int,
                   route: Callable[..., Route] = topk_route,
                   first_expert: int = 0, rows_bound: Optional[int] = None,
                   form: str = "gated_silu"):
    """The local computation of a routed FFN, one function of the tokens, the
    router's scores over its full width and the banks of the experts held
    here: top-k and sort, the grouped products over the experts held, the
    weighted un-sort. ``form`` names what an expert computes
    (:data:`EXPERT_FORMS`): gated-SiLU over ``gate``, ``up``, ``down``, or
    ``relu2``, ``down_e(relu(up_e x)^2)``, with ``gate`` None.

    x: ``[T, d]``; scores: ``[T, E]`` float32 (``route`` says what they are:
    :func:`topk_route` softmax probabilities, :func:`sigmoid_topk_route`
    sigmoid scores chosen under ``bias [E]``); gate, up: ``[H, d, w]``; down:
    ``[H, w, d]``, the experts ``[first_expert, first_expert + H)``. Returns
    ``(y [T, d] float32, group_sizes [H])``: ``y[t] = sum over the slots of t
    whose expert is held of weight * down_e(silu(gate_e x[t]) * up_e x[t])``,
    accumulated in float32 and left so.

    Every expert held (``H == E``, ``rows_bound`` None): the ``T*k`` rows are
    sorted and every one computed. One chip's share (``H < E``): what the
    absent experts would add is left out, and their rows are kept out of the
    buffers too, not only out of the products: held rows sort first, and a
    pass gathers, multiplies and adds back ``rows_bound`` of them in buffers of
    that many rows (``T*k`` where not given: one pass). Dropless for the
    experts held, whatever the router does: a step that routes more than
    ``rows_bound`` rows to them takes as many passes over the same buffers as
    they need (:func:`_held_passes`), so the bound sets the memory and the
    grain of the work, never which rows are computed. The first pass keeps
    what its backward reads, as the two one-pass branches do through plain
    autodiff; only the passes past it, which a step rarely takes, are computed
    again for the backward."""
    from autodist_tpu import telemetry
    n_tokens, d = x.shape
    n_held, width = int(up.shape[0]), int(scores.shape[1])
    n_slots = n_tokens * top_k
    whole = n_held == width and rows_bound is None
    rows_bound = n_slots if rows_bound is None else min(int(rows_bound), n_slots)
    telemetry.gauge("moe.experts").set(n_held)
    telemetry.gauge("moe.top_k").set(int(top_k))
    telemetry.gauge("moe.rows_per_call").set(rows_bound)
    telemetry.gauge("moe.router_width").set(width)
    telemetry.gauge("moe.experts_held").set(n_held)
    telemetry.gauge("moe.rows_bound").set(rows_bound)
    passes = -(-n_slots // rows_bound)       # at most; a step takes what it needs
    telemetry.gauge("moe.passes_max").set(passes)
    telemetry.gauge("moe.passes_kept").set(1)   # pass 0; the others recompute
    # of a pass's four row operations (dispatch, combine, their transposes),
    # how many a kernel of ops/moe_rows.py carries: the share's two sums of a
    # token's rows; its two gathers are XLA's, as all four of the whole
    # bank's are (a permutation has no rows to add up)
    telemetry.gauge("moe.rows.by_kernel").set(0 if whole else 2)
    # scalars the routing still moves one by one by index, forward and
    # transpose: a pass's ``take(weights, kept)`` and its scatter-add back
    # (the chosen scores, the sorted keys and ``inv_perm`` made three more of
    # each layer before they came from a select and from sorts)
    telemetry.gauge("moe.route.indexed_scalar_ops").set(0 if whole else 2)
    # banks an expert has: 3 gated-SiLU (gate, up, down), 2 relu2 (up, down)
    telemetry.gauge("moe.expert_form").set(3 if gate is not None else 2)
    if whole:
        with jax.named_scope("moe.route"):
            r = route(scores, top_k, bias)
        with jax.named_scope("moe.dispatch"):
            rows = _dispatch_rows(x, r.perm, r.inv_perm, top_k)
        out = _expert_mlps(rows, gate, up, down, r.group_sizes, form)
        with jax.named_scope("moe.combine"):
            out = _permute_rows(out, r.inv_perm, r.perm)
            y = jnp.einsum("tk,tkd->td", r.weights,
                           out.reshape(n_tokens, top_k, d),
                           preferred_element_type=jnp.float32)
        return y, r.group_sizes
    with jax.named_scope("moe.route"):
        r = route(scores, top_k, bias, first_expert=first_expert, n_held=n_held)
        offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(r.group_sizes, dtype=jnp.int32)])
        weights = r.weights.reshape(-1)
    if passes == 1:
        y = _held_pass(0, x, weights, gate, up, down, r.perm, offsets, top_k,
                       rows_bound, form)
    else:
        perm = jnp.pad(r.perm, (0, passes * rows_bound - n_slots))
        y = _held_passes(x, weights, gate, up, down, perm, offsets, top_k,
                         rows_bound, form)
    return y, r.group_sizes


class RoutedFFN(nn.Module):
    """Dropless top-k mixture of gated-SiLU experts.

    ``__call__(h)`` takes the block's normalised input in float32 ``[B, S, d]``:
    the router product and softmax read it as it is (at ``HIGHEST`` precision,
    so the choice of experts does not hang on the activation dtype's rounding
    of ``h``), the experts read it cast to ``dtype``. Returns ``(y, aux)`` with
    ``y`` float32 (the weighted sum's accumulator, for a float32 residual) and
    ``aux`` the layer's load-balancing loss ``E * sum_e f_e P_e`` (``f_e`` the
    rows expert ``e`` received over the tokens, ``P_e`` its mean probability)
    and router z-loss ``mean(logsumexp(logits)^2)``."""
    n_experts: int
    top_k: int
    d_expert: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, h):
        from autodist_tpu.parallel.mesh import per_device
        b, s, d = h.shape
        init = nn.initializers.normal(0.02)
        router = self.param("router", init, (d, self.n_experts), jnp.float32)
        bank = [self.param(name, init, shape, jnp.float32) for name, shape in (
            ("gate", (self.n_experts, d, self.d_expert)),
            ("up", (self.n_experts, d, self.d_expert)),
            ("down", (self.n_experts, self.d_expert, d)))]
        if self.is_initializing():
            # Shapes are all that init needs: no kernel is compiled for the
            # handful of positions it runs on.
            zero = jnp.zeros((), jnp.float32)
            return (jnp.zeros((b, s, d), jnp.float32),
                    {"load_balance": zero, "router_z": zero})
        tokens = h.reshape(b * s, d)
        logits = jnp.dot(tokens.astype(jnp.float32), router,
                         precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        y, sizes = per_device(
            functools.partial(routed_experts, top_k=self.top_k),
            (tokens.astype(self.dtype), probs, *bank),
            batched=(True, True, False, False, False))
        # Per device the sizes are of its own tokens: [devices * E] here.
        sizes = sizes.reshape(-1, self.n_experts).sum(axis=0)
        load = jax.lax.stop_gradient(sizes.astype(jnp.float32)) / (b * s)
        aux = {"load_balance": self.n_experts * jnp.sum(load * probs.mean(axis=0)),
               "router_z": jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))}
        return y.reshape(b, s, d), aux


# ------------------------------------------- sigmoid-routed shares and their bias

_INIT = nn.initializers.normal(0.02)


def _dense(features: int, dtype, name: str, init=_INIT) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    param_dtype=jnp.float32, kernel_init=init, name=name)


class GatedMLP(nn.Module):
    """``W_down(silu(W_gate h) * W_up h)``: a dense layer's MLP, a shared
    expert. ``init``: the three matrices' initializer, where a family
    publishes another than ``normal(0.02)``."""
    width: int
    dtype: Any
    init: Callable = _INIT

    @nn.compact
    def __call__(self, h):
        gate = checkpoint_name(
            _dense(self.width, self.dtype, "gate", self.init)(h), KEPT_GATE)
        up = checkpoint_name(
            _dense(self.width, self.dtype, "up", self.init)(h), KEPT_UP)
        return _dense(h.shape[-1], self.dtype, "down", self.init)(
            nn.silu(gate) * up)


class PlainMLP(nn.Module):
    """``W_down act(W_up h)``, two matrices and no gate (``act``: ``relu2``,
    Nemotron-H's): :class:`GatedMLP`'s sibling, a shared expert."""
    width: int
    dtype: Any
    act: Callable = relu2

    @nn.compact
    def __call__(self, h):
        up = checkpoint_name(_dense(self.width, self.dtype, "up")(h), KEPT_UP)
        return _dense(h.shape[-1], self.dtype, "down")(self.act(up))


class RoutedShare(nn.Module):
    """An expert layer's MLP: this chip's share of the layer's sigmoid top-k
    routed experts and, where ``d_shared`` is not zero, a shared expert of
    that width and of the experts' ``form`` beside it, which every token
    passes (:class:`GatedMLP`, or :class:`PlainMLP` under ``"relu2"``;
    parameters under ``shared``). Its own parameters: ``router [d,
    n_experts_routed]``, ``expert_bias [n_experts_routed]`` (float32, zeros)
    and the banks ``gate``, ``up`` ``[held, d, d_expert]``, ``down [held,
    d_expert, d]`` of the experts ``[first_expert_held, first_expert_held +
    experts_held)`` (no ``gate`` under ``form="relu2"``: :data:`EXPERT_FORMS`).
    ``config`` is the family's: those sizes, ``top_k``, ``rows_bound``,
    ``dtype`` and :func:`sigmoid_topk_route`'s normaliser (``route_norm``,
    ``route_scale`` and, where the family sets one, ``route_eps``).

    ``h`` is the float32 normalised input ``[B, S, d]``: the router's product
    and sigmoid read it as it is at ``HIGHEST`` precision, the experts its
    cast to ``dtype``. Returns ``(m [B, S, d] float32, the bias term)``: the
    shared expert's output + the held experts' weighted sum (what the absent
    ones would add is left out), and ``sum_e (b_e - stop_gradient(b_e)) .
    stop_gradient(c_e - mean c) / T``, zero in value, whose gradient with
    respect to ``expert_bias`` is the layer's load error (``c_e``: the rows
    expert ``e`` of the router's whole width received). The loads are sown
    under ``intermediates`` / ``load``, the passes the held rows took under
    ``passes``."""
    config: Any
    d_shared: int = 0
    form: str = "gated_silu"

    @nn.compact
    def __call__(self, h):
        from autodist_tpu.parallel.mesh import per_device, stored_axis
        cfg, form = self.config, self.form
        if form not in EXPERT_FORMS:
            raise ValueError(f"Unknown expert form {form!r}; valid: {EXPERT_FORMS}")
        b, s, d = h.shape
        router_width, held = cfg.n_experts_routed, cfg.experts_held
        if self.d_shared:
            with jax.named_scope("moe.shared"):
                mlp = PlainMLP if form == "relu2" else GatedMLP
                shared = mlp(self.d_shared, cfg.dtype, name="shared")(
                    h.astype(cfg.dtype))
        router = self.param("router", _INIT, (d, router_width), jnp.float32)
        bias = self.param("expert_bias", nn.initializers.zeros, (router_width,),
                          jnp.float32)
        gated = form != "relu2"
        bank = [self.param(name, _INIT, shape, jnp.float32) for name, shape in (
            ("gate", (held, d, cfg.d_expert)), ("up", (held, d, cfg.d_expert)),
            ("down", (held, cfg.d_expert, d)))[0 if gated else 1:]]
        if self.is_initializing():
            # Shapes are all that init needs: no kernel is compiled for the
            # handful of positions it runs on.
            return jnp.zeros((b, s, d), jnp.float32), jnp.zeros((), jnp.float32)
        tokens = h.reshape(b * s, d)
        scores = jax.nn.sigmoid(checkpoint_name(
            jnp.dot(tokens.astype(jnp.float32), router,
                    precision=jax.lax.Precision.HIGHEST), KEPT_ROUTER_LOGITS))
        route = functools.partial(sigmoid_topk_route, **{
            name: getattr(cfg, name) for name in (
                "route_norm", "route_scale", "route_eps", "n_group",
                "topk_group") if hasattr(cfg, name)})
        share = functools.partial(routed_experts, top_k=cfg.top_k, route=route,
                                  first_expert=cfg.first_expert_held,
                                  rows_bound=cfg.rows_bound, form=form)
        # A bank stored as shares over the data axis (strategy.FullySharded)
        # is gathered in ``per_device``'s body, ahead of the share and so of
        # its loop over the later passes (whose trip count differs a chip:
        # no collective may sit in it). Cast in front of that gather, as
        # ``gmm`` would cast behind it: half the bytes move, and the bank's
        # gradient is reduce-scattered in ``dtype`` (each chip's float32 sum
        # rounded once).
        bank = [w.astype(cfg.dtype) if stored_axis(w.shape) is not None else w
                for w in bank]
        y, sizes = per_device(
            share if gated else lambda x, s, *rest: share(x, s, None, *rest),
            (tokens.astype(cfg.dtype), scores, *bank, bias),
            batched=(True, True) + (False,) * (len(bank) + 1))
        # The load every expert of the router's width received, absent ones
        # too: the choice is made here for all of them. (The same top_k as the
        # route's; the compiler keeps one.)
        choice = jax.lax.stop_gradient(scores + bias)
        if getattr(cfg, "n_group", 1) > 1:
            choice = group_limited(choice, cfg.n_group, cfg.topk_group)
        _, chosen = jax.lax.top_k(choice, cfg.top_k)
        load = jnp.sum(chosen[..., None] == jnp.arange(router_width), axis=(0, 1),
                       dtype=jnp.float32)
        bias_term = jnp.sum((bias - jax.lax.stop_gradient(bias))
                            * jax.lax.stop_gradient(load - load.mean())) / (b * s)
        # Per device the sizes are of its own tokens, [devices * held]: the
        # passes are those of the device that took most.
        held_rows = sizes.reshape(-1, held).sum(axis=1)
        slots = b * s * cfg.top_k // held_rows.size
        bound = slots if cfg.rows_bound is None else min(int(cfg.rows_bound), slots)
        passes = _passes(held_rows, bound).max()
        # for whoever applies with mutable=["intermediates"] (tools/afmoe_load.py)
        self.sow("intermediates", "load", load)
        self.sow("intermediates", "passes", passes.astype(jnp.int32))
        y = y.reshape(b, s, d)
        return (shared.astype(jnp.float32) + y if self.d_shared else y), bias_term


def check_share(config) -> None:
    """What a family's ``__post_init__`` asks of its share: ``top_k`` inside
    the router's width, the held experts inside it and, where the family has
    leading dense layers, their number inside the stack."""
    if not 0 <= getattr(config, "n_dense_layers", 0) <= config.n_layers:
        raise ValueError("n_dense_layers must be in [0, n_layers]")
    if not 1 <= config.top_k <= config.n_experts_routed:
        raise ValueError("top_k must be in [1, n_experts_routed]")
    if not (0 <= config.first_expert_held and config.experts_held >= 1
            and config.first_expert_held + config.experts_held
            <= config.n_experts_routed):
        raise ValueError("the experts held must lie inside the router's width")


def balanced_optimizer(learning_rate, load_balance_coeff: float,
                       weights: Optional[Callable] = None):
    """AdamW (or ``weights(learning_rate)``) for every leaf but the
    ``expert_bias`` ones, which take ``b += delta - mean(delta)``, ``delta =
    -load_balance_coeff * sign(d loss / d b)``: with
    :class:`RoutedShare`'s loss term the published aux-loss-free
    balancing rule, as an optax transformation."""
    import optax

    def balance(grads, state, params=None):
        del params
        signs = jax.tree_util.tree_map(jnp.sign, grads)
        return jax.tree_util.tree_map(
            lambda s: -load_balance_coeff * (s - s.mean()), signs), state

    def labels(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, _: "bias" if getattr(path[-1], "key", None)
            == "expert_bias" else "weights", params)

    return optax.multi_transform(
        {"weights": (weights or optax.adamw)(learning_rate),
         "bias": optax.GradientTransformation(lambda params: optax.EmptyState(),
                                              balance)},
        labels)


def _expert_blocks(tree) -> list:
    """Names of the blocks of ``tree`` that hold an expert layer (``block_<i>``
    with a ``moe`` entry), in layer order."""
    return sorted((name for name in tree if "moe" in tree[name]),
                  key=lambda name: int(name.rsplit("_", 1)[1]))


def _sown(intermediates, what: str) -> jax.Array:
    return jnp.stack([intermediates[name]["moe"][what][0]
                      for name in _expert_blocks(intermediates)])


def sown_loads(intermediates) -> jax.Array:
    """``[expert layers, router width]`` from the ``intermediates`` an
    ``apply(..., mutable=["intermediates"])`` returns: the rows every expert
    of every expert layer received, absent experts too, in layer order."""
    return _sown(intermediates, "load")


def sown_passes(intermediates) -> jax.Array:
    """``[expert layers]`` int32 from the same ``intermediates``: the passes
    each expert layer's held rows took (``ceil(held rows / rows_bound)`` on
    the device that took most; every one past the first is recomputed for the
    backward)."""
    return _sown(intermediates, "passes")


def expert_loads(model: nn.Module, params, tokens) -> jax.Array:
    """:func:`sown_loads` of one forward pass over ``tokens [B, L]``."""
    _, sown = model.apply({"params": params}, tokens, return_hidden=True,
                          mutable=["intermediates"])
    return sown_loads(sown["intermediates"])


def balance_expert_bias(model: nn.Module, params, batches, coeffs):
    """The parameters with every ``expert_bias`` moved by the balancing rule
    alone, no weight touched: for each coefficient in ``coeffs``, in turn on
    the next of ``batches`` (``[B, L]`` token arrays, cycled), ``b += delta -
    mean(delta)`` with ``delta = coeff * sign(mean(c) - c_e)`` in every expert
    layer at once. A randomly initialised router loads its experts very
    unevenly (the normalised residual stream has a large component common to
    all tokens, which every token's scores share); a trained one is held
    level by this rule. A falling ``coeffs`` brings the first to the second's
    loads in tens of forward passes."""
    from autodist_tpu import telemetry
    names = _expert_blocks(params)

    @jax.jit
    def moved(params, tokens, coeff):      # the biases alone: nothing else is copied
        loads = expert_loads(model, params, tokens)
        delta = coeff * jnp.sign(loads.mean(axis=1, keepdims=True) - loads)
        return [params[name]["moe"]["expert_bias"] + d - d.mean()
                for name, d in zip(names, delta)]

    # Once a build, and tens of fenced forward passes: a set-up phase.
    with telemetry.phase("setup.expert_bias_balance_s"):
        for i, coeff in enumerate(coeffs):
            # fenced: the host must not run passes ahead of the device (each
            # holds a forward's activations)
            biases = jax.block_until_ready(
                moved(params, batches[i % len(batches)], jnp.float32(coeff)))
            params = dict(params)
            for name, bias in zip(names, biases):
                params[name] = dict(params[name], moe=dict(
                    params[name]["moe"], expert_bias=bias))
        telemetry.counter("setup.expert_bias_passes").inc(len(coeffs))
    return params
