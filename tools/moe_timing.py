#!/usr/bin/env python3
"""Time the OLMoE cell's kernels alone, on the chip, at the cell's shapes.

The instrument behind the choice of the grouped matmul (``ops/grouped_matmul.py``
against ``jax.lax.ragged_dot``) and of its tiles, and the stand-alone readings of
the two kernels the cell shares with the GPT-2 cells at shapes those never run.
Every time is device time from a profiler trace of ``--calls`` calls (the busy
union of all device operations a call, and the self time under each kernel's
name); one JSON line a measurement on standard output.

    python tools/moe_timing.py                         # every phase
    python tools/moe_timing.py --phases gmm --tiles 256,512 --tiles 512,512
    python tools/moe_timing.py --phases flash,xent,flips
    python tools/moe_timing.py --phases rows            # ~2 min
    python tools/moe_timing.py --phases route           # ~5 min

Phases: ``gmm`` (forward, and dX + dW, at the gate/up and the down
shape: 131,072 rows in 64 groups as a top-8 of random logits sorts them, against
``ragged_dot`` on the same arguments), ``flash`` (4 x 4,096 x 16 x 128 causal,
forward + backward), ``xent`` (the fused head at 16,384 x 2,048 x 50,304),
``flips`` (the share of top-8 choices that differ between bfloat16 and float32
activations, one sequence through the published widths at depth 1),
``gmmshare`` (not in a whole run; the grouped matmul at one chip's share of
Nemotron's ``relu2`` experts: 6,144 rows a pass of which a top-6 of 128 random
scores sends ~3,072 to the 8 experts held, 2,688 x 1,856 and back, widths
that are 21 x 128 and 14.5 x 128; ``--col-tiles 2688:128`` (may repeat) sets
the output columns a block of that width takes, against the rule's own),
``gmmcheck`` (``gmm`` and its gradients against ``ragged_dot`` in float32 at the
highest precision, relative L2), ``gradcheck`` (the benchmark's check of the cell by parameter: each one's share
of the squared difference from the reference's gradient and of its norm),
``rows`` (one chip's share, not OLMoE's whole bank: the four row operations of
``models/moe.py`` ``_held_pass`` (dispatch, combine and their transposes) as
XLA's gather and scatter-add and as the two kernels of ``ops/moe_rows.py``,
``[T, d]`` in and ``[R, d]`` out with every reshape and layout copy they
bring, at both share cells' shapes: T 16,384 / R 16,384 / top-4 of 64 and T
8,192 / R 8,192 / top-8 of 128, 8 experts held, d 2,048, the held rows those
of a real top-k of random scores; ``plan`` is the sort by token the two
combines of a pass share; ``ns_a_held_row`` is the busy time over the held
rows), ``route`` (not in a whole run; the routing alone, ``models/moe.py``
``sigmoid_topk_route`` / ``topk_route`` as they stand beside the form they had
before PR 51 (``take_along_axis`` or ``top_k``'s own values for the chosen
scores, ``argsort`` and then ``flat[perm]`` for the sorted keys, a scatter for
``inv_perm``), forward and forward + gradient with respect to the scores, the
two compared value for value, at the shapes of ``lfm2-pretrain-8k``,
``trinity-pretrain-8k``, ``olmoe-pretrain-4k`` (the whole bank) and one chip
of ``mimo-sharded4-8k``; and, as lines of their own, ``inv_perm`` as a
scatter and as a second sort by key (the whole bank's: a share reads none),
and a pass's ``take(weights, kept)`` beside the weights sorted by key with a
sort for its transpose (not taken: it loses under 8,192 rows a pass)).
Needs the TPU: a time from the CPU's interpreter says nothing.
"""

import argparse
import glob
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, EXPERTS, TOP_K, D_MODEL, D_EXPERT, VOCAB = 131072, 64, 8, 2048, 1024, 50304


def device_ms(fn, args, calls: int):
    """(busy ms a call, {group: self ms a call}) of ``fn(*args)``."""
    import jax

    from benchmark import trace_reduce
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            jax.block_until_ready([fn(*args) for _ in range(calls)])
        files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        summary = trace_reduce.summarize(trace_reduce.read_xplane(files[0]))
    device = summary.devices[min(summary.devices)]
    groups = sorted(device.by_group.items(), key=lambda kv: -kv[1])[:8]
    return (device.busy_s / calls * 1e3,
            {g: s / calls * 1e3 for g, s in groups})


def group_sizes(seed: int = 0):
    """Rows an expert receives when the top 8 of 64 random logits route
    16,384 tokens: ragged, none empty, mean 2,048."""
    import jax
    import jax.numpy as jnp
    logits = jax.random.normal(jax.random.PRNGKey(seed), (ROWS // TOP_K, EXPERTS))
    _, chosen = jax.lax.top_k(logits, TOP_K)
    return jnp.bincount(chosen.reshape(-1), length=EXPERTS).astype(jnp.int32)


def phase_gmm(calls, tiles):
    import jax
    import jax.numpy as jnp

    from autodist_tpu.ops import grouped_matmul
    sizes = group_sizes()
    yield {"phase": "gmm", "group_sizes_min_max": [int(sizes.min()),
                                                    int(sizes.max())]}
    implementations = {
        "ragged_dot": lambda x, w, s: jax.lax.ragged_dot(
            x, w.astype(x.dtype), s, preferred_element_type=jnp.float32
        ).astype(x.dtype)}
    for tile in tiles or [None]:
        def kernel(x, w, s, tile=tile):
            if tile is not None:
                grouped_matmul.ROW_TILE, grouped_matmul.DW_ROW_TILE = tile
            return grouped_matmul.gmm(x, w, s)
        implementations[f"gmm{'' if tile is None else tile}"] = kernel
    for shape_name, (k, n) in (("gate_up", (D_MODEL, D_EXPERT)),
                               ("down", (D_EXPERT, D_MODEL))):
        keys = jax.random.split(jax.random.PRNGKey(1), 3)
        x = jax.random.normal(keys[0], (ROWS, k), jnp.bfloat16)
        w = jax.random.normal(keys[1], (EXPERTS, k, n), jnp.float32) * 0.02
        ct = jax.random.normal(keys[2], (ROWS, n), jnp.bfloat16)
        flop = 2.0 * ROWS * k * n
        for name, fn in implementations.items():
            fwd = jax.jit(fn)
            both = jax.jit(jax.grad(
                lambda x, w, s, ct, fn=fn: jnp.sum(
                    fn(x, w, s).astype(jnp.float32) * ct.astype(jnp.float32)),
                argnums=(0, 1)))
            # the gradient of a linear function needs no forward product:
            # the compiler drops it, and "bwd" is dX + dW alone
            for what, f, args, products in (("fwd", fwd, (x, w, sizes), 1),
                                            ("bwd", both, (x, w, sizes, ct), 2)):
                busy, groups = device_ms(f, args, calls)
                yield {"phase": "gmm", "shape": shape_name, "impl": name,
                       "what": what, "busy_ms": busy,
                       "tflops_of_busy": products * flop / busy / 1e9,
                       "groups_ms": groups}


SHARE_ROWS, SHARE_HELD, SHARE_WIDTH, SHARE_TOP_K = 6144, 8, 128, 6
SHARE_D_MODEL, SHARE_D_EXPERT = 2688, 1856
COL_TILES = []          # --col-tiles: {width: columns a block}, one a sweep


def share_group_sizes(seed: int = 0):
    """Rows the 8 experts held receive when the top 6 of 128 random scores
    route 8,192 tokens: ragged, ~384 each, the pass's tail empty."""
    import jax
    import jax.numpy as jnp
    scores = jax.random.normal(jax.random.PRNGKey(seed), (8192, SHARE_WIDTH))
    _, chosen = jax.lax.top_k(scores, SHARE_TOP_K)
    return jnp.bincount(chosen.reshape(-1), length=SHARE_WIDTH
                        )[:SHARE_HELD].astype(jnp.int32)


def phase_gmmshare(calls, _tiles):
    import jax
    import jax.numpy as jnp

    from autodist_tpu.ops import grouped_matmul
    sizes = share_group_sizes()
    yield {"phase": "gmmshare", "held_rows": int(sizes.sum()),
           "rows": SHARE_ROWS}
    rule = grouped_matmul._col_tile
    implementations = {
        "ragged_dot": (None, lambda x, w, s: jax.lax.ragged_dot(
            x, w.astype(x.dtype), s, preferred_element_type=jnp.float32
        ).astype(x.dtype))}
    for override in [{}] + COL_TILES:
        name = "gmm" + "".join(f"[{k}:{v}]" for k, v in override.items())
        implementations[name] = (override, grouped_matmul.gmm)
    for shape_name, (k, n) in (("up", (SHARE_D_MODEL, SHARE_D_EXPERT)),
                               ("down", (SHARE_D_EXPERT, SHARE_D_MODEL))):
        keys = jax.random.split(jax.random.PRNGKey(1), 3)
        x = jax.random.normal(keys[0], (SHARE_ROWS, k), jnp.bfloat16)
        w = jax.random.normal(keys[1], (SHARE_HELD, k, n), jnp.float32) * 0.02
        ct = jax.random.normal(keys[2], (SHARE_ROWS, n), jnp.bfloat16)
        flop = 2.0 * int(sizes.sum()) * k * n
        for name, (override, fn) in implementations.items():
            if override is not None:
                grouped_matmul._col_tile = (
                    lambda width, override=override:
                    override.get(width) or rule(width))
            fwd = jax.jit(fn)
            both = jax.jit(jax.grad(
                lambda x, w, s, ct, fn=fn: jnp.sum(
                    fn(x, w, s).astype(jnp.float32) * ct.astype(jnp.float32)),
                argnums=(0, 1)))
            for what, f, args, products in (("fwd", fwd, (x, w, sizes), 1),
                                            ("bwd", both, (x, w, sizes, ct), 2)):
                try:
                    busy, groups = device_ms(f, args, calls)
                except Exception as e:  # noqa: BLE001 — a sweep goes on past refused tiles
                    yield {"phase": "gmmshare", "shape": shape_name,
                           "impl": name, "what": what,
                           "refused": str(e).splitlines()[0][:300]}
                    continue
                yield {"phase": "gmmshare", "shape": shape_name, "impl": name,
                       "what": what, "busy_ms": busy,
                       "tflops_of_busy_held_rows": products * flop / busy / 1e9,
                       "groups_ms": groups}
    grouped_matmul._col_tile = rule


def phase_gmmcheck(_calls, _tiles):
    """``gmm`` and its two gradients on the chip against ``ragged_dot`` in
    float32 at the highest precision, at the cell's shapes: relative L2."""
    import jax
    import jax.numpy as jnp

    from autodist_tpu.ops.grouped_matmul import gmm
    sizes = group_sizes()
    for shape_name, (k, n) in (("gate_up", (D_MODEL, D_EXPERT)),
                               ("down", (D_EXPERT, D_MODEL))):
        keys = jax.random.split(jax.random.PRNGKey(1), 3)
        x = jax.random.normal(keys[0], (ROWS, k), jnp.bfloat16)
        w = (jax.random.normal(keys[1], (EXPERTS, k, n), jnp.float32) * 0.02
             ).astype(jnp.bfloat16).astype(jnp.float32)
        ct = jax.random.normal(keys[2], (ROWS, n), jnp.bfloat16)

        def exact(x, w):
            return jax.lax.ragged_dot(x.astype(jnp.float32), w, sizes,
                                      precision=jax.lax.Precision.HIGHEST)

        def both(fn):
            return jax.jit(lambda x, w: (fn(x, w),) + jax.grad(
                lambda x, w: jnp.sum(fn(x, w).astype(jnp.float32)
                                     * ct.astype(jnp.float32)),
                argnums=(0, 1))(x, w))
        got = both(lambda x, w: gmm(x, w, sizes))(x, w)
        want = both(exact)(x, w)
        rel = [float(jnp.linalg.norm((g.astype(jnp.float32)
                                      - r.astype(jnp.float32)).ravel())
                     / jnp.linalg.norm(r.astype(jnp.float32).ravel()))
               for g, r in zip(got, want)]
        yield {"phase": "gmmcheck", "shape": shape_name,
               "rel_l2_y_dx_dw": rel}


def phase_flash(calls, _):
    import jax
    import jax.numpy as jnp

    from autodist_tpu.ops.flash_attention import flash_attention
    q, k, v = (jax.random.normal(key, (4, 4096, 16, 128), jnp.bfloat16)
               for key in jax.random.split(jax.random.PRNGKey(0), 3))
    grad = jax.jit(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    busy, groups = device_ms(grad, (q, k, v), calls)
    yield {"phase": "flash", "shape": [4, 4096, 16, 128], "busy_ms": busy,
           "groups_ms": groups}


def phase_xent(calls, _):
    import jax
    import jax.numpy as jnp

    from autodist_tpu.ops.fused_xent import fused_softmax_xent
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(keys[0], (ROWS // TOP_K, D_MODEL), jnp.bfloat16)
    w = jax.random.normal(keys[1], (D_MODEL, VOCAB), jnp.float32) * 0.02
    targets = jax.random.randint(keys[2], (ROWS // TOP_K,), 0, VOCAB)
    grad = jax.jit(jax.grad(lambda h, w: fused_softmax_xent(
        h, w, targets).mean(), argnums=(0, 1)))
    busy, groups = device_ms(grad, (h, w), calls)
    yield {"phase": "xent", "shape": [ROWS // TOP_K, D_MODEL, VOCAB],
           "busy_ms": busy, "groups_ms": groups}


def phase_flips(_calls, _tiles):
    """The router's input under bfloat16 activations against float32 ones,
    same weights and tokens: how many top-8 choices change."""
    import jax
    import jax.numpy as jnp

    from autodist_tpu.models import olmoe

    def choices(dtype, params=None):
        cfg = olmoe.OlmoeConfig(n_layers=1, dtype=dtype, attention_impl="flash")
        model = olmoe.Olmoe(cfg)
        if params is None:
            params = olmoe.init_params(cfg, jax.random.PRNGKey(0))[1]
        tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 4096), 0,
                                    cfg.vocab_size)
        _, state = jax.jit(lambda p, t: model.apply(
            {"params": p}, t, return_hidden=True, capture_intermediates=(
                lambda module, _: module.name == "ln_moe")))(params, tokens)
        h = state["intermediates"]["block_0"]["ln_moe"]["__call__"][0]
        logits = jnp.dot(h.reshape(-1, cfg.d_model).astype(jnp.float32),
                         params["block_0"]["moe"]["router"],
                         precision=jax.lax.Precision.HIGHEST)
        return jax.lax.top_k(jax.nn.softmax(logits), cfg.top_k)[1], params

    low, params = choices(jnp.bfloat16)
    high, _ = choices(jnp.float32, params)
    same = (low[:, :, None] == high[:, None, :]).any(axis=-1)    # [T, k]
    yield {"phase": "flips", "tokens": int(low.shape[0]),
           "slots_flipped_share": float(1.0 - same.mean()),
           "tokens_with_a_flip_share": float(1.0 - same.all(axis=-1).mean())}


def phase_gradcheck(_calls, _tiles):
    """The benchmark's check of ``olmoe-pretrain-4k`` taken apart: the
    system's gradient against the plain reference's, by parameter, for the
    cell's configuration and for variants of it that take one source of
    rounding away at a time."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from autodist_tpu.models import olmoe
    from benchmark import harness
    cell = harness.load_cell("olmoe-pretrain-4k", ROOT)
    family = cell.load_module("families", "olmoe")
    reference = cell.load_module("reference", "olmoe")
    built = family.build(cell.config, cell.traffic, 11, 4)
    sample = {k: jnp.asarray(v) for k, v in built.sample.items()}
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(jax.grad(lambda p, b: reference.loss(
            p, b, **built.reference_config)))(built.params, sample)
    cfg = family.model_config(cell.config)
    variants = (
        ("the cell", {}, None),
        ("dot attention", {"attention_impl": "dot"}, None),
        ("XLA head", {"fused_head": False}, None),
        ("float32 activations", {"dtype": jnp.float32}, None),
        ("float32 activations, XLA head and attention, highest precision",
         {"dtype": jnp.float32, "fused_head": False, "attention_impl": "dot"},
         "highest"),
    )
    for name, changes, precision in variants:
        loss_fn = olmoe.make_loss_fn(olmoe.Olmoe(dataclasses.replace(cfg, **changes)))
        with jax.default_matmul_precision(precision or "default"):
            grads = jax.jit(jax.grad(loss_fn))(built.params, sample)
        rows = {jax.tree_util.keystr(path): (float(jnp.sum(jnp.square(g - r))),
                                             float(jnp.sum(jnp.square(r))))
                for (path, g), r in zip(
                    jax.tree_util.tree_leaves_with_path(grads),
                    jax.tree_util.tree_leaves(ref))}
        diff, norm = (sum(x) for x in zip(*rows.values()))
        yield {"phase": "gradcheck", "variant": name,
               "grad_rel_l2": (diff / norm) ** 0.5,
               "rel_l2_by_parameter": {
                   k: round((d / n) ** 0.5, 4) for k, (d, n) in rows.items()
                   if n > 1e-4 * norm}}
        del grads


# (cell, tokens, rows a pass, router width, top_k); 8 experts held, d 2,048
ROW_SHAPES = (("lfm2-pretrain-8k", 16384, 16384, 64, 4),
              ("trinity-pretrain-8k", 8192, 8192, 128, 8))


def phase_rows(calls, _tiles):
    """XLA's four row operations of one pass of a share against the two
    kernels, operation by operation, the same arguments to both."""
    import jax
    import jax.numpy as jnp

    from autodist_tpu.models import moe
    from autodist_tpu.ops import moe_rows
    f32, bf16 = jnp.float32, jnp.bfloat16

    def scatter_add(values, token, n_tokens):
        return jnp.zeros((n_tokens, values.shape[-1]), f32).at[token].add(values)

    for cell, n_tokens, bound, width, top_k in ROW_SHAPES:
        keys = jax.random.split(jax.random.PRNGKey(0), 6)
        scores = jax.nn.sigmoid(jax.random.normal(keys[0], (n_tokens, width)))
        r = moe.sigmoid_topk_route(scores, top_k, first_expert=0, n_held=8)
        count = jnp.minimum(r.group_sizes.sum(), bound).astype(jnp.int32)
        kept = r.perm[:bound]
        token = kept // top_k
        # as ``_held_pass`` had it before the kernels: zero past the held rows
        weight = jnp.where(jnp.arange(bound) < count,
                           jnp.take(r.weights.reshape(-1), kept), 0.0)
        held = int(count)
        x = jax.random.normal(keys[1], (n_tokens, D_MODEL), bf16)
        out = jax.random.normal(keys[2], (bound, D_MODEL), bf16)
        # the grouped matmul's dX: zero past the held rows
        g_rows = jnp.where(jnp.arange(bound)[:, None] < count,
                           jax.random.normal(keys[3], (bound, D_MODEL), bf16), 0)
        g = jax.random.normal(keys[4], (n_tokens, D_MODEL), f32)
        plan = jax.jit(lambda t, c: moe_rows.combine_plan(t, c, n_tokens))(
            token, count)
        yield {"phase": "rows", "cell": cell, "tokens": n_tokens,
               "rows_bound": bound, "held_rows": held}

        def scaled(taken, out, weight):
            return ((weight[:, None] * taken).astype(out.dtype),
                    jnp.sum(taken * out.astype(f32), axis=-1))

        measurements = (
            ("plan", "sort", lambda t, c: moe_rows.combine_plan(t, c, n_tokens),
             (token, count)),
            ("1 dispatch (bf16 gather)", "xla",
             lambda x, t: jnp.take(x, t, axis=0), (x, token)),
            ("1 dispatch (bf16 gather)", "kernel", moe_rows.moe_rows_gather,
             (x, token, count)),
            ("1 dispatch (bf16 gather)", "xla, indices promised in bounds",
             lambda x, t: x.at[t].get(mode="promise_in_bounds"),
             (x, token)),
            ("2 combine (f32 scatter-add of weighted bf16 rows)", "xla",
             lambda o, w, t: scatter_add(w[:, None] * o.astype(f32), t, n_tokens),
             (out, weight, token)),
            ("2 combine (f32 scatter-add of weighted bf16 rows)", "kernel",
             lambda o, w, t, c, p: moe_rows.moe_rows_combine(
                 o, w, t, c, n_tokens, p), (out, weight, token, count, plan)),
            ("3 combine's transpose (f32 gather, scaled)", "xla",
             lambda g, o, w, t: scaled(jnp.take(g, t, axis=0), o, w),
             (g, out, weight, token)),
            ("3 combine's transpose (f32 gather, scaled)", "kernel",
             lambda g, o, w, t, c: scaled(moe_rows.moe_rows_gather(g, t, c), o, w),
             (g, out, weight, token, count)),
            ("3 combine's transpose (f32 gather, scaled)",
             "xla, indices promised in bounds",
             lambda g, o, w, t: scaled(
                 g.at[t].get(mode="promise_in_bounds"), o, w),
             (g, out, weight, token)),
            ("4 dispatch's transpose (f32 scatter-add of bf16 rows)", "xla",
             lambda gr, t: scatter_add(gr.astype(f32), t, n_tokens).astype(bf16),
             (g_rows, token)),
            ("4 dispatch's transpose (f32 scatter-add of bf16 rows)", "kernel",
             lambda gr, t, c, p: moe_rows.moe_rows_combine(
                 gr, None, t, c, n_tokens, p, dtype=bf16),
             (g_rows, token, count, plan)),
        )
        results = {}
        for what, impl, fn, args in measurements:
            fn = jax.jit(fn)
            results[what, impl] = jax.device_get(fn(*args))
            busy, groups = device_ms(fn, args, calls)
            yield {"phase": "rows", "cell": cell, "what": what, "impl": impl,
                   "busy_ms": busy, "ns_a_held_row": busy * 1e6 / max(held, 1),
                   "groups_ms": groups}
        for what in sorted({w for w, impl in results if impl == "kernel"}):
            got, want = results[what, "kernel"], results[what, "xla"]
            # a gather's rows past the held ones differ by design: XLA
            # fetches what they name, the kernel writes zeros
            n = held if what[0] in "13" else None
            worst = max(float(jnp.max(jnp.abs(
                jnp.asarray(a[:n], f32) - jnp.asarray(b[:n], f32)), initial=0.0))
                for a, b in zip(jax.tree_util.tree_leaves(got),
                                jax.tree_util.tree_leaves(want)))
            yield {"phase": "rows", "cell": cell, "what": what,
                   "max_abs_kernel_minus_xla": worst}


# (cell, tokens, router width, top_k, experts held (None: the whole bank,
# ``topk_route``), rows a pass)
ROUTE_SHAPES = (("lfm2-pretrain-8k", 16384, 64, 4, 8, 16384),
                ("trinity-pretrain-8k", 8192, 128, 8, 8, 8192),
                ("olmoe-pretrain-4k", 16384, 64, 8, None, 131072),
                ("mimo-sharded4-8k", 8192, 256, 8, 8, 4096))


def route_before(scores, k, bias, n_held, chosen="take"):
    """The routing as it stood before PR 51, its by-index forms kept:
    ``take_along_axis`` for the chosen scores (``chosen="take"``;
    ``"top_k"``: ``top_k``'s own values, as ``topk_route`` had them),
    ``argsort`` followed by ``flat[perm]``, and ``inv_perm`` by a scatter.
    Returns ``Route``'s fields."""
    import jax
    import jax.numpy as jnp
    choice = scores if bias is None else scores + jax.lax.stop_gradient(bias)
    weights, indices = jax.lax.top_k(choice, k)
    if chosen == "take":
        weights = jnp.take_along_axis(scores, indices, axis=-1)
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    flat = indices.reshape(-1).astype(jnp.int32)
    width = scores.shape[1]
    n_held = width if n_held is None else n_held
    if n_held != width:
        flat = jnp.where(flat < n_held, flat, n_held)
    perm = jnp.argsort(flat, stable=True).astype(jnp.int32)
    rows = jnp.arange(flat.size, dtype=jnp.int32)
    inv_perm = jnp.zeros_like(perm).at[perm].set(rows, unique_indices=True)
    ends = jnp.searchsorted(flat[perm], jnp.arange(n_held, dtype=jnp.int32),
                            side="right").astype(jnp.int32)
    return indices, weights, jnp.diff(ends, prepend=0), perm, inv_perm


def phase_route(calls, _tiles):
    """The routing of one expert layer, forward and forward + gradient, as it
    stood and as it stands, and two by-index scalar operations alone beside
    the sorts that replace them (``inv_perm``: taken; a pass's weights: not)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from autodist_tpu import telemetry
    from autodist_tpu.models import moe

    def sort_by(keys, values):
        return jax.lax.sort((keys, values), num_keys=1)[1]

    @jax.custom_vjp
    def sorted_weights(weights, perm, inv_perm):
        """``weights[perm]`` with no gather, and no scatter in its transpose."""
        return sort_by(inv_perm, weights)

    sorted_weights.defvjp(
        lambda weights, perm, inv_perm: (sort_by(inv_perm, weights), perm),
        lambda perm, g: (sort_by(perm, g), None, None))

    for cell, n_tokens, width, top_k, n_held, bound in ROUTE_SHAPES:
        whole = n_held is None
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        logits = jax.random.normal(keys[0], (n_tokens, width))
        scores = jax.nn.softmax(logits) if whole else jax.nn.sigmoid(logits)
        bias = None if whole else 0.05 * jax.random.normal(keys[1], (width,))
        n_slots = n_tokens * top_k
        ct = jax.random.normal(keys[2], (n_tokens, top_k))
        yield {"phase": "route", "cell": cell, "tokens": n_tokens,
               "router_width": width, "top_k": top_k, "choices": n_slots,
               "experts_held": width if whole else n_held}

        def now(scores, bias):
            if whole:
                return moe.topk_route(scores, top_k)
            return moe.sigmoid_topk_route(scores, top_k, bias, n_held=n_held)

        def before(scores, bias):
            return route_before(scores, top_k, bias, n_held,
                                "top_k" if whole else "take")

        forms = {"before": before, "now": now}

        def used(route):
            # what the step reads of a routing: a share never reads inv_perm
            return tuple(route)[:None if whole else 4]

        results = {}
        for impl, form in forms.items():
            fwd = jax.jit(lambda s, b, form=form: used(form(s, b)))

            def loss(s, b, form=form):
                r = used(form(s, b))
                return jnp.sum(r[1] * ct), (r[0],) + r[2:]
            both = jax.jit(jax.grad(loss, has_aux=True))
            for what, fn in (("fwd", fwd), ("fwd + grad", both)):
                results[what, impl] = jax.device_get(fn(scores, bias))
                busy, groups = device_ms(fn, (scores, bias), calls)
                yield {"phase": "route", "cell": cell, "what": what,
                       "impl": impl, "busy_ms": busy, "groups_ms": groups}

        r = jax.jit(now)(scores, bias)
        rows = jnp.arange(n_slots, dtype=jnp.int32)
        candidates = [
            ("inv_perm", "scatter", lambda perm: jnp.zeros_like(perm).at[perm].set(
                rows, unique_indices=True), (r.perm,)),
            ("inv_perm", "sort", lambda perm: sort_by(perm, rows), (r.perm,))]
        if not whole:
            count = jnp.minimum(r.group_sizes.sum(), bound).astype(jnp.int32)
            flat_weights = r.weights.reshape(-1)
            g = jax.random.normal(keys[3], (bound,))

            def masked(weight):
                return jnp.where(jnp.arange(bound) < count, weight, 0.0)

            def taken(weights, perm):
                return masked(jnp.take(weights, perm[:bound]))

            def by_sort(weights, perm):
                return masked(sorted_weights(
                    weights, perm, sort_by(perm, rows))[:bound])

            for impl, fn in (("take", taken), ("sorts", by_sort)):
                candidates += [
                    ("a pass's weights, fwd", impl, fn, (flat_weights, r.perm)),
                    ("a pass's weights, fwd + grad", impl,
                     jax.value_and_grad(lambda w, p, fn=fn: jnp.sum(fn(w, p) * g)),
                     (flat_weights, r.perm))]
        for what, impl, fn, args in candidates:
            fn = jax.jit(fn)
            results[what, impl] = jax.device_get(fn(*args))
            busy, groups = device_ms(fn, args, calls)
            yield {"phase": "route", "cell": cell, "what": what, "impl": impl,
                   "busy_ms": busy, "groups_ms": groups}
        # the gauges a step's trace of this layer sets (shapes only: nothing runs)
        bank = [jax.ShapeDtypeStruct(shape, jnp.bfloat16) for shape in (
            (width if whole else n_held, D_MODEL, D_EXPERT),) * 2
            + ((width if whole else n_held, D_EXPERT, D_MODEL),)]
        jax.eval_shape(
            lambda x, s, *b: moe.routed_experts(
                x, s, *b, bias, top_k=top_k,
                route=moe.topk_route if whole else moe.sigmoid_topk_route,
                rows_bound=None if whole else bound),
            jax.ShapeDtypeStruct((n_tokens, D_MODEL), jnp.bfloat16), scores, *bank)
        print(cell, {k: v for k, v in telemetry.snapshot().items()
                     if k.startswith("moe.")}, file=sys.stderr)
        # every form of one thing against its first, value for value, on the
        # chip: equal to the bit unless the compiler adds a float32 sum up in
        # another order (the weights' normaliser is a sum of top_k terms)
        for what in dict.fromkeys(w for w, _ in results):
            (_, first), *others = [(impl, jax.tree_util.tree_leaves(v))
                                   for (w, impl), v in results.items() if w == what]
            yield {"phase": "route", "cell": cell, "what": what,
                   "max_abs_difference_from_the_first_form": {
                       impl: max(float(np.max(np.abs(a.astype(np.float64) - b)))
                                 for a, b in zip(first, other))
                       for impl, other in others}}


PHASES = {"gmmcheck": phase_gmmcheck, "gradcheck": phase_gradcheck,
          "gmm": phase_gmm, "flash": phase_flash, "xent": phase_xent,
          "flips": phase_flips, "rows": phase_rows}
# not in a whole run (PERF.md's 12 minutes are the phases above)
EXTRA_PHASES = {"gmmshare": phase_gmmshare, "route": phase_route}


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--phases", default=",".join(PHASES))
    parser.add_argument("--tiles", action="append", default=[],
                        help="ROW_TILE,DW_ROW_TILE override; may repeat")
    parser.add_argument("--col-tiles", action="append", default=[],
                        help="width:columns[,width:columns] a block of gmm "
                             "takes in gmmshare; may repeat")
    parser.add_argument("--calls", type=int, default=5)
    args = parser.parse_args(argv)
    COL_TILES.extend(dict(tuple(int(x) for x in pair.split(":"))
                          for pair in t.split(",")) for t in args.col_tiles)
    sys.path.insert(0, ROOT)
    import jax
    if jax.default_backend() != "tpu":
        raise SystemExit(f"needs the TPU, the backend is {jax.default_backend()!r}")
    tiles = [tuple(int(x) for x in t.split(",")) for t in args.tiles]
    for name in args.phases.split(","):
        for record in {**PHASES, **EXTRA_PHASES}[name](args.calls, tiles):
            print(json.dumps(record), flush=True)


if __name__ == "__main__":
    main()
