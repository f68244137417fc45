"""Plain float32 reference of Nemotron-H's training loss, written from the
published ``config.json`` of ``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``
and the Mamba-2 paper (Dao & Gu 2024), not from the system's code: no kernel,
no chunked scan, no carried state, no sort, no compaction, no bfloat16, no
flax. The parameter tree is read by name.

``x0 = E[tokens]``. Every layer is one mixer, ``x = x + mixer(RMSNorm(x))``,
of the kind ``pattern[l]`` names:

``M`` (Mamba-2). ``[z | xBC | dt] = h W_in``; ``xBC_t = silu(sum_j w[:, j]
xBC_{t-(K-1)+j} + b)`` (depthwise, causal, zeros before the sequence);
``[x | B | C] = xBC``; ``dt = softplus(dt + dt_bias)``, ``a_t = -exp(A_log)
dt_t`` a head. The recurrence ``S_t = exp(a_t) S_{t-1} + dt_t x_t B_t^T``,
``y_t = S_t C_t + D x_t`` is computed in its **exact quadratic form**::

    y_t = sum_{s<=t} exp(sum_{s<r<=t} a_r) (C_t . B_s) dt_s x_s + D x_t

every (query, key) pair of a sequence, ``QUERY_BLOCK`` queries at a time
against all keys under ``jax.checkpoint`` (the literal recurrence would keep
8,192 states of 2 MB a sequence for its backward; a chunked form is what the
system computes). The exponent is never the difference of two long running
sums: for a query block that starts at ``t0`` it is ``c_t - c_s`` with ``c``
the sum of ``a`` counted *from t0*, forward for ``r >= t0`` and backward (as
a negative number) for ``r < t0``, so the pairs that matter, the near ones,
are sums of few terms. Head ``h`` reads the ``B`` and ``C`` of group ``h //
(H / G)``. Then ``y = RMSNorm(y * silu(z))`` over each of the ``G`` runs of
``d_inner / G`` channels, times one weight, and ``y W_out``.

``E``. ``s = sigmoid(h.Wr)`` over the router's full width, the ``top_k``
largest ``s + expert_bias`` chosen, the weights ``s`` of the chosen divided
by their sum (+ ``route_eps``) and times ``route_scale``; an expert is
``W_down relu(W_up h)^2``; the shared expert is added for every token. **The
share** and **the bias** as ``reference/afmoe.py`` writes them: each held
expert is applied to every token, one a scan step, under the token's weight
for it (zero where it is not among the token's ``top_k``); the bias enters
the loss as the zero-valued term whose gradient is the load error.

``*``. q, k, v without bias, norm or positional embedding; softmax attention
at 1/sqrt(head_dim) over the keys ``j <= i``, query head ``n`` reading KV
head ``n // group`` (``reference/afmoe.py`` ``banded_attention`` without a
window); the output projection.

After the last layer RMSNorm and the untied head; mean next-token
cross-entropy. Each layer sits under ``jax.checkpoint``, so that one sequence
of 8,192 fits the chip beside the parameters; that changes no number.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import banded_attention
from benchmark.reference.common import mean_nll
from benchmark.reference.olmoe import rms_norm

QUERY_BLOCK = 256


def relu2_mlp(h, up, down):
    return jnp.square(jax.nn.relu(h @ up)) @ down


def quadratic_ssm(x, dt, a, bmat, cmat, d_skip):
    """x: [b, L, H, P]; dt, a: [b, L, H] (a = A dt < 0); bmat, cmat:
    [b, L, H, N] (already repeated to the heads); d_skip: [H]. The exact
    quadratic form, ``QUERY_BLOCK`` queries at a time."""
    b, length, heads, p = x.shape
    block = QUERY_BLOCK if length % QUERY_BLOCK == 0 else length
    position = jnp.arange(length)
    xd = x * dt[..., None]

    @jax.checkpoint
    def one_block(args):
        c_blk, first = args                              # [b, block, H, N]
        before = (position < first)[None, :, None]
        forward = jnp.cumsum(jnp.where(before, 0.0, a), axis=1)
        a_before = jnp.where(before, a, 0.0)
        backward = jnp.flip(jnp.cumsum(jnp.flip(a_before, 1), axis=1), 1)
        c = forward - (backward - a_before)              # sum of a from `first`
        c = c.swapaxes(1, 2)                             # [b, H, L]
        rows = first + jnp.arange(block)
        c_rows = jax.lax.dynamic_slice_in_dim(c, first, block, axis=2)
        seg = c_rows[..., :, None] - c[..., None, :]     # [b, H, block(t), L(s)]
        visible = position[None, :] <= rows[:, None]
        decay = jnp.exp(jnp.where(visible, seg, -jnp.inf))
        scores = jnp.einsum("bthn,bshn->bhts", c_blk, bmat)
        return jnp.einsum("bhts,bshp->bthp", decay * scores, xd)

    blocks = cmat.reshape(b, length // block, block, heads, -1).swapaxes(0, 1)
    y = jax.lax.map(one_block, (blocks, jnp.arange(0, length, block)))
    return y.swapaxes(0, 1).reshape(x.shape) + d_skip[:, None] * x


def mamba2(h, p, *, heads, head_dim, n_groups, d_state, eps):
    b, length, _ = h.shape
    d_inner, group_width = heads * head_dim, n_groups * d_state
    z, xbc, dt = jnp.split(h @ p["in_proj"]["kernel"],
                           [d_inner, 2 * d_inner + 2 * group_width], axis=-1)
    taps = p["conv"]                                      # [channels, K]
    k = taps.shape[1]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(taps[:, j] * padded[:, j:j + length]
                          for j in range(k)) + p["conv_bias"])
    x, bmat, cmat = jnp.split(xbc, [d_inner, d_inner + group_width], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    per_head = lambda t: jnp.repeat(  # noqa: E731
        t.reshape(b, length, n_groups, d_state), heads // n_groups, axis=2)
    y = quadratic_ssm(x.reshape(b, length, heads, head_dim), dt,
                      -jnp.exp(p["A_log"]) * dt, per_head(bmat), per_head(cmat),
                      p["D"])
    gated = y.reshape(b, length, d_inner) * jax.nn.silu(z)
    runs = gated.reshape(b, length, n_groups, -1)
    runs = runs * jax.lax.rsqrt(jnp.mean(jnp.square(runs), axis=-1,
                                         keepdims=True) + eps)
    return (runs.reshape(b, length, d_inner) * p["norm"]) @ p["out_proj"]["kernel"]


def attention(h, p, *, n_heads, n_kv_heads, head_dim):
    b, length, _ = h.shape
    heads = lambda t, n: t.reshape(b, length, n, head_dim)  # noqa: E731
    q = heads(h @ p["query"]["kernel"], n_heads)
    k = heads(h @ p["key"]["kernel"], n_kv_heads)
    v = heads(h @ p["value"]["kernel"], n_kv_heads)
    group = n_heads // n_kv_heads
    ctx = banded_attention(q, jnp.repeat(k, group, axis=2),
                           jnp.repeat(v, group, axis=2), None)
    return ctx.reshape(b, length, n_heads * head_dim) @ p["out"]["kernel"]


def mixture(h, p, *, top_k, route_norm, route_scale, route_eps,
            first_expert_held):
    """h: [T, d] -> (the shared expert + the held experts' weighted sum, the
    bias term)."""
    scores = jax.nn.sigmoid(h @ p["router"])
    width = scores.shape[-1]
    choice = scores + jax.lax.stop_gradient(p["expert_bias"])
    kth = jnp.sort(choice, axis=-1)[:, width - top_k]
    chosen = choice >= kth[:, None]        # a tie at the k-th place has measure zero
    weights = jnp.where(chosen, scores, 0.0)
    if route_norm:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + route_eps)
    weights = weights * route_scale
    held = p["up"].shape[0]
    mine = weights[:, first_expert_held:first_expert_held + held]

    @jax.checkpoint
    def one_expert(up, down, weight):
        return weight[:, None] * relu2_mlp(h, up, down)

    y, _ = jax.lax.scan(lambda total, e: (total + one_expert(*e), None),
                        jnp.zeros_like(h), (p["up"], p["down"], mine.T))
    load = jax.lax.stop_gradient(jnp.sum(chosen.astype(jnp.float32), axis=0))
    bias = p["expert_bias"]
    bias_term = jnp.sum((bias - jax.lax.stop_gradient(bias))
                        * (load - load.mean())) / h.shape[0]
    shared = p["shared"]
    return (relu2_mlp(h, shared["up"]["kernel"], shared["down"]["kernel"]) + y,
            bias_term)


def block(p, x, *, kind, eps, ssm, attn, route):
    b, length, d = x.shape
    h = rms_norm(x, p["norm"]["scale"], eps)
    if kind == "M":
        return x + mamba2(h, p["mamba"], eps=eps, **ssm), 0.0
    if kind == "*":
        return x + attention(h, p["attn"], **attn), 0.0
    m, bias_term = mixture(h.reshape(b * length, d), p["moe"], **route)
    return x + m.reshape(b, length, d), bias_term


def loss(params, batch, *, pattern: str, mamba_heads: int, mamba_head_dim: int,
         n_groups: int, d_state: int, n_heads: int, n_kv_heads: int,
         head_dim: int, top_k: int, rms_eps: float, route_norm: bool,
         route_scale: float, route_eps: float, first_expert_held: int):
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"]["embedding"][inputs]
    kinds = dict(
        ssm=dict(heads=mamba_heads, head_dim=mamba_head_dim, n_groups=n_groups,
                 d_state=d_state),
        attn=dict(n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim),
        route=dict(top_k=top_k, route_norm=route_norm, route_scale=route_scale,
                   route_eps=route_eps, first_expert_held=first_expert_held))
    bias_terms = 0.0
    for i, kind in enumerate(pattern):
        x, term = jax.checkpoint(
            lambda p, x, kind=kind: block(p, x, kind=kind, eps=rms_eps, **kinds))(
            params[f"block_{i}"], x)
        bias_terms = bias_terms + term
    x = rms_norm(x, params["norm_f"]["scale"], rms_eps)
    return mean_nll(x @ params["lm_head"]["kernel"], targets) + bias_terms
