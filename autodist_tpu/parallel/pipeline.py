"""Pipeline parallelism over the mesh ``pipe`` axis (GPipe and 1F1B schedules).

Beyond reference parity: the reference explicitly scoped pipeline parallelism out
(``docs/design/architecture.rst:49-51``, SURVEY.md §2.2). The TPU-native design is
the collective-permute formulation: stage parameters are sharded ``P("pipe", ...)``
on their leading stage dimension, and inside a ``jax.shard_map`` manual region over
the ``pipe`` axis each device runs its stage on a stream of microbatches, handing
activations to the next stage with ``lax.ppermute``.

Three schedules:

- **GPipe** (:func:`pipelined`): a single forward ``lax.scan`` of
  ``num_microbatches + n_stages - 1`` ticks; reverse-mode autodiff through the
  scan yields the backward pipeline automatically. Simple, but autodiff stores
  every tick's residuals, so live activation memory grows with
  ``num_microbatches``.
- **1F1B** (:func:`pipelined_value_and_grad`): each tick runs one forward AND
  one backward slot per stage; a microbatch's backward starts as soon as its
  activations return from downstream, so at most ``2*n_stages - 1`` microbatch
  inputs are live per stage — activation memory is O(n_stages), independent of
  the microbatch count. Backward recomputes the stage forward from its saved
  INPUT (``jax.vjp`` inside the tick), the standard remat trade: one extra
  forward per microbatch buys the O(n_stages) residency. The loss (tail) runs
  inside the schedule at the last stage, which is what makes the interleaving
  possible; total ticks = ``num_microbatches + 2*(n_stages - 1)`` versus
  GPipe's ``2*(num_microbatches + n_stages - 1)``.
- **Interleaved 1F1B** (:func:`interleaved_value_and_grad`): each device holds
  ``v`` model CHUNKS (virtual stages) instead of one fat stage — chunk ``c``
  of ``V = S*v`` lives on device ``c mod S`` — so pipeline ticks are
  thin-chunk-sized. Fill/drain overhead drops from ``2(S-1)`` fat ticks
  (``= 2v(S-1)`` thin-tick equivalents of compute) to ``(v+1)S - 2`` thin
  ticks — a ``~(v+1)/2v`` bubble ratio, approaching half for large ``v`` —
  at the cost of a deeper input ring (``O(v*S)`` saved microbatch inputs
  per device vs ``O(S)``). The schedule is closed-form:
  device ``r``'s ``i``-th forward slot processes microbatch group ``i //
  (S*v)``, chunk ``(i % (S*v)) // S``, group position ``i % S``; backwards
  mirror it in reverse chunk order, offset by ``delay(r) = 2(S-1) + (v-1)S -
  r``; every activation hop then lands exactly one ``ppermute`` (with ring
  wrap) ahead of its consumer.

All are written for the *partial-manual* shard_map mode (``axis_names=
{"pipe"}``): every other mesh axis stays under automatic SPMD partitioning, so
pipeline composes with data parallelism (batch stays sharded on ``data``) and the
other strategies.
"""

from typing import Callable

import jax
import jax.numpy as jnp

from autodist_tpu import const
from autodist_tpu.parallel import mesh as mesh_lib

PyTree = object


def pipeline_apply(stage_fn: Callable, stage_params: PyTree, x_mb: jax.Array,
                   axis: str = const.MESH_AXIS_PIPE) -> jax.Array:
    """GPipe loop body — must run inside a shard_map manual over ``axis``.

    stage_fn(stage_params, x) -> y applies one pipeline stage to one microbatch
    (``stage_params`` is this device's shard: leading stage dim of size 1).
    x_mb: [num_microbatches, mb_batch, ...] activations entering stage 0,
    replicated along ``axis`` (only rank 0 reads them; the transpose of that read
    routes the input gradient back correctly). Returns the last stage's outputs,
    [num_microbatches, mb_batch, ...], replicated along ``axis``.
    """
    n_stages = jax.lax.psum(1, axis)
    rank = jax.lax.axis_index(axis)
    n_mb = x_mb.shape[0]

    if n_stages == 1:
        # Degenerate single-stage pipeline: no schedule needed.
        def apply_one(carry, x):
            return carry, stage_fn(stage_params, x)
        _, out = jax.lax.scan(apply_one, 0, x_mb)
        return out

    shift_pairs = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        state, outputs = carry
        mb = jax.lax.dynamic_index_in_dim(
            x_mb, jnp.clip(t, 0, n_mb - 1), 0, keepdims=False)
        x = jnp.where(rank == 0, mb, state)
        y = stage_fn(stage_params, x)
        # The last stage starts emitting results at tick n_stages-1.
        take = (t >= n_stages - 1) & (rank == n_stages - 1)
        idx = jnp.clip(t - (n_stages - 1), 0, n_mb - 1)
        prev = jax.lax.dynamic_index_in_dim(outputs, idx, 0, keepdims=False)
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs, jnp.where(take, y, prev), idx, 0)
        nxt = jax.lax.ppermute(y, axis, shift_pairs)
        return (nxt, outputs), None

    init = (jnp.zeros_like(x_mb[0]), jnp.zeros_like(x_mb))
    (_, outputs), _ = jax.lax.scan(tick, init, jnp.arange(n_mb + n_stages - 1))
    # Broadcast the last stage's results to every pipe rank so downstream
    # (replicated) computation — the LM head, the loss — sees them everywhere.
    mask = (rank == n_stages - 1).astype(outputs.dtype)
    return jax.lax.psum(outputs * mask, axis)


def onef_oneb_apply(stage_fn: Callable, tail_fn: Callable, stage_params: PyTree,
                    tail_params: PyTree, x_mb: jax.Array, targets_mb: PyTree,
                    axis: str = const.MESH_AXIS_PIPE):
    """1F1B loop body — must run inside a shard_map manual over ``axis``.

    ``stage_fn(stage_params, x) -> y`` is one stage on one microbatch;
    ``tail_fn(tail_params, y, target) -> scalar`` is the post-pipeline head +
    loss for one microbatch (run at the LAST stage, inside the schedule — the
    placement that lets a microbatch's backward start while later microbatches
    are still filling). Returns ``(mean_loss, stage_grads, tail_grads,
    x_grads)``; ``x_grads`` is [M, ...] (d loss / d x_mb, for callers with
    trainable pre-pipeline computation).

    Schedule (S stages, M microbatches, tick t): stage r runs the forward of
    microbatch ``t - r`` and the backward of microbatch ``t - (2S - 2 - r)``
    (each when in [0, M)). Forward activations hop r -> r+1, backward input
    grads hop r -> r-1, one ppermute each per tick. A microbatch's input is
    held from its forward to its backward — at most ``2(S-1-r) + 1`` live per
    stage, hence the O(n_stages) activation footprint.
    """
    n_stages = jax.lax.psum(1, axis)
    n_mb = x_mb.shape[0]

    def mb_at(tree, k):
        return jax.tree_util.tree_map(
            lambda l: jax.lax.dynamic_index_in_dim(l, k, 0, keepdims=False),
            tree)

    if n_stages == 1:
        # Degenerate: plain per-microbatch value_and_grad accumulation.
        def one(carry, k):
            gs, gt, gx, acc = carry
            def full(sp, tp, x):
                return tail_fn(tp, stage_fn(sp, x), mb_at(targets_mb, k))
            (l, (dgs, dgt, dgx)) = jax.value_and_grad(full, argnums=(0, 1, 2))(
                stage_params, tail_params, x_mb[k])
            gs = jax.tree_util.tree_map(jnp.add, gs, dgs)
            gt = jax.tree_util.tree_map(jnp.add, gt, dgt)
            gx = jax.lax.dynamic_update_index_in_dim(gx, dgx, k, 0)
            return (gs, gt, gx, acc + l), None
        zeros_s = jax.tree_util.tree_map(jnp.zeros_like, stage_params)
        zeros_t = jax.tree_util.tree_map(jnp.zeros_like, tail_params)
        (gs, gt, gx, acc), _ = jax.lax.scan(
            one, (zeros_s, zeros_t, jnp.zeros_like(x_mb), jnp.zeros(())),
            jnp.arange(n_mb))
        scale = 1.0 / n_mb
        return (acc * scale,
                jax.tree_util.tree_map(lambda g: g * scale, gs),
                jax.tree_util.tree_map(lambda g: g * scale, gt),
                gx * scale)

    # General case: exactly the interleaved schedule with one chunk per
    # device — the slot arithmetic, ring sizing, delay offset, masks, and
    # ownership psums all reduce to the plain-1F1B formulas at n_chunks=1
    # (pinned by tests), so ONE tick body serves both schedules.
    return interleaved_onef_oneb_apply(stage_fn, tail_fn, stage_params,
                                       tail_params, x_mb, targets_mb,
                                       n_chunks=1, axis=axis)


def interleaved_onef_oneb_apply(stage_fn: Callable, tail_fn: Callable,
                                stage_params: PyTree, tail_params: PyTree,
                                x_mb: jax.Array, targets_mb: PyTree,
                                n_chunks: int,
                                axis: str = const.MESH_AXIS_PIPE):
    """Interleaved-1F1B loop body — must run inside a shard_map manual over
    ``axis``. ``stage_params`` is this device's LOCAL chunk block: leading dim
    ``n_chunks`` (= v), local index ``j`` holding VIRTUAL stage ``j*S + r``
    (device-major layout; :func:`interleave_chunk_layout` converts from
    virtual-stage order). Returns ``(mean_loss, stage_grads, tail_grads,
    x_grads)`` with ``stage_grads`` in the same local layout.

    Per thin-tick, a device runs ONE chunk forward and ONE chunk backward
    (masked in fill/drain). Slot -> (group, chunk, position) index arithmetic
    and the ``delay(r)`` backward offset are chosen so every forward hop
    ``c -> c+1`` and backward hop ``c -> c-1`` — including the ring wraps
    ``S-1 -> 0`` (forward, entering the next chunk group) and ``0 -> S-1``
    (backward) — is produced exactly one tick before its consumer reads it
    (see the module docstring for the derivation)."""
    n_stages = jax.lax.psum(1, axis)
    rank = jax.lax.axis_index(axis)
    v = n_chunks
    n_mb = x_mb.shape[0]
    if v > 1 and n_mb % n_stages:
        # The slot decomposition advances microbatches in groups of S; a
        # ragged final group would silently process some (mb, chunk) pairs
        # twice and skip others — finite, plausible, WRONG gradients.
        raise ValueError(
            f"interleaved 1F1B needs num_microbatches divisible by n_stages "
            f"({n_mb} % {n_stages} != 0); pad the microbatch count")
    sv = n_stages * v
    total_slots = n_mb * v              # forward (= backward) slots per device

    def mb_at(tree, k):
        return jax.tree_util.tree_map(
            lambda l: jax.lax.dynamic_index_in_dim(l, k, 0, keepdims=False),
            tree)

    def chunk_at(tree, j):
        # Keep the size-1 leading dim: stage_fn's contract (shared with plain
        # 1F1B) is a per-device block whose leading stage dim is 1.
        return jax.tree_util.tree_map(
            lambda l: jax.lax.dynamic_slice_in_dim(l, j, 1, axis=0), tree)

    # Max saved-input lifetime: T_b - T_f at r=0, j=0 (see docstring), +1.
    ring_size = 2 * (n_stages - 1) + 2 * (v - 1) * n_stages + 1
    delay = 2 * (n_stages - 1) + (v - 1) * n_stages - rank
    # Ring wraps (forward S-1 -> 0, backward 0 -> S-1) only exist to carry a
    # microbatch across chunk-group transitions; at v=1 there are none and
    # the wrap payloads would be pure dead inter-device traffic every tick.
    if v > 1:
        fwd_pairs = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        bwd_pairs = [((i + 1) % n_stages, i) for i in range(n_stages)]
    else:
        fwd_pairs = [(i, i + 1) for i in range(n_stages - 1)]
        bwd_pairs = [(i + 1, i) for i in range(n_stages - 1)]

    def decompose_f(idx):
        g, rem = idx // sv, idx % sv
        return g * n_stages + rem % n_stages, rem // n_stages   # (mb, chunk)

    def decompose_b(idx):
        g, rem = idx // sv, idx % sv
        return g * n_stages + rem % n_stages, v - 1 - rem // n_stages

    def tick(carry, t):
        a_recv, g_recv, ring, gs, gt, gx_buf, loss_acc = carry

        # ---- F slot ------------------------------------------------------
        f_idx = t - rank
        f_valid = (f_idx >= 0) & (f_idx < total_slots)
        f_idx_c = jnp.clip(f_idx, 0, total_slots - 1)
        m_f, j_f = decompose_f(f_idx_c)
        c_f = j_f * n_stages + rank                      # virtual stage id
        x_in = jnp.where(c_f == 0,
                         jax.lax.dynamic_index_in_dim(
                             x_mb, jnp.clip(m_f, 0, n_mb - 1), 0,
                             keepdims=False),
                         a_recv)
        y = stage_fn(chunk_at(stage_params, j_f), x_in)
        slot_f = jnp.mod(f_idx_c, ring_size)
        kept = jax.lax.dynamic_index_in_dim(ring, slot_f, 0, keepdims=False)
        ring = jax.lax.dynamic_update_index_in_dim(
            ring, jnp.where(f_valid, x_in, kept), slot_f, 0)

        # ---- B slot ------------------------------------------------------
        b_idx = t - delay
        b_valid = (b_idx >= 0) & (b_idx < total_slots)
        b_idx_c = jnp.clip(b_idx, 0, total_slots - 1)
        m_b, j_b = decompose_b(b_idx_c)
        c_b = j_b * n_stages + rank
        # The saved input of (m_b, chunk j_b) went into the ring under ITS
        # forward slot index.
        f_of_b = (m_b // n_stages) * sv + j_b * n_stages + m_b % n_stages
        x_saved = jax.lax.dynamic_index_in_dim(
            ring, jnp.mod(f_of_b, ring_size), 0, keepdims=False)
        params_b = chunk_at(stage_params, j_b)
        y_b, vjp = jax.vjp(stage_fn, params_b, x_saved)
        tgt = mb_at(targets_mb, jnp.clip(m_b, 0, n_mb - 1))
        is_last = c_b == sv - 1                          # loss-owning stage

        # The tail (head matmul + loss + its VJP — the vocab-sized work for
        # LM models) contributes ONLY at valid last-stage slots; lax.cond
        # skips it elsewhere instead of computing-then-masking — without the
        # gate every rank/chunk/tick would pay it, and interleaving
        # multiplies the tick count by v.
        def run_tail(args):
            tp, y, t_ = args
            return jax.value_and_grad(tail_fn, argnums=(0, 1))(tp, y, t_)

        def skip_tail(args):
            # Zeros in run_tail's exact output structure/dtypes (eval_shape:
            # no compute traced) — cond branches must match precisely.
            shapes = jax.eval_shape(run_tail, args)
            return jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), shapes)

        loss_k, (d_tail, d_y) = jax.lax.cond(
            b_valid & is_last, run_tail, skip_tail, (tail_params, y_b, tgt))
        g_y = jnp.where(is_last, d_y, g_recv)
        d_stage, d_x = vjp(g_y)
        upd = b_valid

        def acc_chunk(acc, g):
            # g rides the [1, ...] leading block shape chunk_at produced.
            cur = jax.lax.dynamic_slice_in_dim(acc, j_b, 1, axis=0)
            return jax.lax.dynamic_update_slice_in_dim(
                acc, cur + jnp.where(upd, g, 0), j_b, axis=0)

        gs = jax.tree_util.tree_map(acc_chunk, gs, d_stage)
        gt = jax.tree_util.tree_map(
            lambda acc, g: acc + jnp.where(upd & is_last, g, 0), gt, d_tail)
        loss_acc = loss_acc + jnp.where(upd & is_last, loss_k, 0.0)
        k_x = jnp.clip(m_b, 0, n_mb - 1)
        prev = jax.lax.dynamic_index_in_dim(gx_buf, k_x, 0, keepdims=False)
        gx_buf = jax.lax.dynamic_update_index_in_dim(
            gx_buf, jnp.where(upd & (c_b == 0), d_x, prev), k_x, 0)

        a_next = jax.lax.ppermute(y, axis, fwd_pairs)
        g_next = jax.lax.ppermute(d_x, axis, bwd_pairs)
        return (a_next, g_next, ring, gs, gt, gx_buf, loss_acc), None

    zeros_s = jax.tree_util.tree_map(jnp.zeros_like, stage_params)
    zeros_t = jax.tree_util.tree_map(jnp.zeros_like, tail_params)
    init = (
        jnp.zeros_like(x_mb[0]),
        jnp.zeros_like(x_mb[0]),
        jnp.zeros((ring_size,) + x_mb.shape[1:], x_mb.dtype),
        zeros_s, zeros_t,
        jnp.zeros_like(x_mb),
        jnp.zeros(()),
    )
    # Last backward: r=0, b_idx = total_slots - 1 -> tick delay(0) + that.
    n_ticks = total_slots + 2 * (n_stages - 1) + (v - 1) * n_stages
    (_, _, _, gs, gt, gx_buf, loss_acc), _ = jax.lax.scan(
        tick, init, jnp.arange(n_ticks))

    scale = 1.0 / n_mb
    last_rank = n_stages - 1                 # stage V-1 lives on device S-1
    loss = jax.lax.psum(
        loss_acc * (rank == last_rank).astype(loss_acc.dtype), axis) * scale
    gt = jax.tree_util.tree_map(
        lambda g: jax.lax.psum(g * (rank == last_rank).astype(g.dtype), axis)
        * scale, gt)
    gx = jax.lax.psum(gx_buf * (rank == 0).astype(gx_buf.dtype), axis) * scale
    gs = jax.tree_util.tree_map(lambda g: g * scale, gs)
    return loss, gs, gt, gx


def interleave_chunk_layout(tree: PyTree, n_stages: int, n_chunks: int,
                            inverse: bool = False) -> PyTree:
    """Permute leading-dim-``V`` leaves between VIRTUAL-stage order (chunk
    ``c`` at row ``c`` — the natural model layout) and the DEVICE-MAJOR order
    :func:`interleaved_value_and_grad` shards (row ``r*v + j`` = virtual stage
    ``j*S + r``, so ``P(axis)`` on dim 0 gives device ``r`` exactly its
    chunks). Apply once at init (and ``inverse=True`` on returned grads if
    you want them back in virtual order) — NOT inside the step, where the
    cross-device gather would cost every tick."""
    import numpy as _np
    idx = _np.asarray(chunk_perm(n_stages, n_chunks, inverse=inverse))
    return jax.tree_util.tree_map(lambda l: jnp.take(l, idx, axis=0), tree)


def chunk_perm(n_stages: int, n_chunks: int, inverse: bool = False):
    """THE device-major <-> virtual row permutation (one definition, shared
    by :func:`interleave_chunk_layout` and the model-layer layout helpers):
    ``perm[row]`` = source row. Forward: device-major row ``r*v + j`` reads
    virtual row ``j*S + r``; inverse: virtual row ``j*S + r`` reads
    device-major row ``r*v + j``."""
    v, s = n_chunks, n_stages
    if inverse:
        return [(row % s) * v + row // s for row in range(s * v)]
    return [(row % v) * s + row // v for row in range(s * v)]


def interleaved_value_and_grad(stage_fn: Callable, tail_fn: Callable,
                               n_stages: int, n_chunks: int,
                               axis: str = const.MESH_AXIS_PIPE,
                               mesh=None) -> Callable:
    """Wrap :func:`interleaved_onef_oneb_apply` in the partial-manual
    shard_map.

    Returns ``f(stage_params, tail_params, x_mb, targets_mb) -> (mean_loss,
    stage_grads, tail_grads, x_grads)``. ``stage_params`` leaves carry a
    leading dim ``V = n_stages * n_chunks`` in DEVICE-MAJOR layout (use
    :func:`interleave_chunk_layout` to convert from virtual-stage order),
    sharded over ``axis``; grads come back in the same layout. ``n_chunks=1``
    is exactly the plain 1F1B schedule."""
    from jax.sharding import PartitionSpec as P

    def f(stage_params, tail_params, x_mb, targets_mb):
        m, specs = _pipe_mesh_and_specs("interleaved_value_and_grad", mesh,
                                        axis, n_stages, stage_params,
                                        stage_rows=n_stages * n_chunks)
        tail_zero = jax.tree_util.tree_map(lambda _: P(), tail_params)
        tgt_zero = jax.tree_util.tree_map(lambda _: P(), targets_mb)
        return jax.shard_map(
            lambda sp, tp, x, tg: interleaved_onef_oneb_apply(
                stage_fn, tail_fn, sp, tp, x, tg, n_chunks, axis=axis),
            mesh=m,
            in_specs=(specs, tail_zero, P(), tgt_zero),
            out_specs=(P(), specs, tail_zero, P()),
            axis_names={axis}, check_vma=False,
        )(stage_params, tail_params, x_mb, targets_mb)

    return f


def pipelined_value_and_grad(stage_fn: Callable, tail_fn: Callable,
                             n_stages: int, axis: str = const.MESH_AXIS_PIPE,
                             mesh=None) -> Callable:
    """Wrap :func:`onef_oneb_apply` (the 1F1B schedule) in the partial-manual
    shard_map.

    Returns ``f(stage_params, tail_params, x_mb, targets_mb) ->
    (mean_loss, stage_grads, tail_grads, x_grads)``. ``stage_params`` leaves
    carry a leading stage dimension of size ``n_stages`` (sharded over
    ``axis``); ``tail_params`` (head + loss parameters) are replicated;
    ``x_mb``/``targets_mb`` are [num_microbatches, mb_batch, ...]. Must run
    under ``jit``. Keep GPipe (:func:`pipelined` + autodiff) for the simple
    mode; choose 1F1B when activation memory, not schedule simplicity, is the
    constraint.
    """
    from jax.sharding import PartitionSpec as P

    def f(stage_params, tail_params, x_mb, targets_mb):
        m, specs = _pipe_mesh_and_specs("pipelined_value_and_grad", mesh,
                                        axis, n_stages, stage_params)
        tail_zero = jax.tree_util.tree_map(lambda _: P(), tail_params)
        tgt_zero = jax.tree_util.tree_map(lambda _: P(), targets_mb)
        return jax.shard_map(
            lambda sp, tp, x, tg: onef_oneb_apply(stage_fn, tail_fn, sp, tp,
                                                  x, tg, axis=axis),
            mesh=m,
            in_specs=(specs, tail_zero, P(), tgt_zero),
            out_specs=(P(), specs, tail_zero, P()),
            axis_names={axis}, check_vma=False,
        )(stage_params, tail_params, x_mb, targets_mb)

    return f


def _pipe_mesh_and_specs(fn_name: str, mesh, axis: str, n_stages: int,
                         stage_params, stage_rows: int = None):
    """Shared mesh resolution + stage-size validation + P(axis) spec build for
    the schedule wrappers. Without the size check a mismatched mesh silently
    runs only the stage groups the pipe axis covers — finite loss, most
    layers skipped. ``stage_rows`` (interleaved: S*v) validates the params'
    leading dim when it differs from the axis size."""
    from jax.sharding import PartitionSpec as P

    m = mesh if mesh is not None else _ambient_mesh()
    mesh_stages = dict(m.shape).get(axis, 1)
    if mesh_stages != n_stages:
        raise ValueError(
            f"{fn_name}(n_stages={n_stages}) needs mesh axis {axis!r} of that "
            f"size, but the mesh has {axis}={mesh_stages}; size the mesh with "
            f"the Pipeline strategy or a matching resource-spec mesh")
    rows = n_stages if stage_rows is None else stage_rows
    for path, leaf in jax.tree_util.tree_flatten_with_path(stage_params)[0]:
        shape = getattr(leaf, "shape", None)
        if shape is not None and (len(shape) == 0 or shape[0] != rows):
            raise ValueError(
                f"{fn_name}: stage_params leaves need leading dim {rows}, "
                f"got {shape} at {jax.tree_util.keystr(path)}")
    return m, jax.tree_util.tree_map(lambda _: P(axis), stage_params)


def _ambient_mesh():
    mesh = mesh_lib.ambient_mesh()
    if mesh is None:
        raise RuntimeError(
            "pipelined() needs a mesh: pass one explicitly or call inside a "
            "`with mesh:` block (DistributedRunner.run steps under one)")
    return mesh


def pipelined(stage_fn: Callable, n_stages: int, axis: str = const.MESH_AXIS_PIPE,
              mesh=None) -> Callable:
    """Wrap :func:`pipeline_apply` in the partial-manual shard_map.

    Returns ``f(stage_params, x_mb) -> y_mb`` where ``stage_params`` leaves carry a
    leading stage dimension of size ``n_stages`` (sharded over ``axis``) and all
    other mesh axes remain automatic. ``mesh`` defaults to the ambient mesh
    context (the runner steps inside ``with self.mesh``). Must run under ``jit``
    (partial-manual shard_map is trace-time only).
    """
    from jax.sharding import PartitionSpec as P

    def f(stage_params, x_mb):
        m, specs = _pipe_mesh_and_specs("pipelined", mesh, axis, n_stages,
                                        stage_params)
        return jax.shard_map(
            lambda p, x: pipeline_apply(stage_fn, p, x, axis=axis),
            mesh=m, in_specs=(specs, P()), out_specs=P(),
            axis_names={axis}, check_vma=False,
        )(stage_params, x_mb)

    return f
