#!/usr/bin/env python3
"""Take ``jamba2-sharded4-16k``'s check apart: the whole gradient's distance
of the Jamba model to the plain float32 reference
(``benchmark/reference/jamba.py``), as ``benchmark/jobs/train.py``
``check_against_reference`` measures it (limits 5e-3 on the loss, 5e-2 on the
gradient), with one thing computed in a lower precision at a time. On ONE
device at the published widths and a depth and length it holds, or
(``--cell``) the job's own check itself on the cell's own parameters, sample
and four chips, a variant at a time:

    python tools/jamba_gradcheck.py --layers 4 --positions 4096            # the chip, ~3 min
    JAX_PLATFORMS=cpu python tools/jamba_gradcheck.py --layers 12 --positions 256   # here, ~4 min
    python tools/jamba_gradcheck.py --cell jamba2-sharded4-16k --seeds N --variants cell state16   # four chips, ~8 min

``--variants`` (default all): ``cell`` the model as the cell runs it (bfloat16
operands, float32 accumulators, ``dt`` and the scan's state float32);
``dt16``: the step ``dt`` rounded to bfloat16 before the scan; ``state16``:
the scan's ``[E, N]`` state rounded to bfloat16 after every token (the plain
token loop, so slow). On the chip the model runs its kernels (``ssm_impl``
pallas, flash, the fused head), on the CPU the plain paths, whose roundings
fall at the same places. One JSON line a (seed, variant); ``--seeds`` may
repeat. ``--precise-layers N`` overrides ``models/jamba.py``
``PRECISE_LAYERS`` (0: every layer's forward in bfloat16, as the cell ran
until the driver drew 0.0504 of the limit's 0.05). The distance grows like
the square root of the depth because the first layer's rounding is the whole
stream's and every later layer's Jacobian is taken that far off (with 0
precise layers 0.016 at 2 layers and 0.046 at 12 on the CPU, 0.0215 at 4
layers and 4,096 positions on the chip, 0.048-0.050 in the cell at 14 and
16,384; with 1: 0.024 at 12, 0.0234 in the cell), so read a variant against
``cell`` at the same depth, not against the limit.
"""

import argparse
import json
import os
import sys
import time

VARIANTS = ("cell", "dt16", "state16")


def _lowered(jamba, variant):
    """``selective_scan`` as ``models/jamba.py`` calls it, with one input or
    the state in bfloat16."""
    import jax
    import jax.numpy as jnp
    real = jamba.selective_scan
    if variant == "cell":
        return real
    # not a cast there and back: XLA on the TPU drops that pair (excess
    # precision is allowed), and the variant would run the cell's program
    bf16 = lambda t: jax.lax.reduce_precision(t, 8, 7)  # noqa: E731
    if variant == "dt16":
        return lambda x, dt, *rest, **kw: real(x, bf16(dt), *rest, **kw)

    def rounded_state(x, dt, A, B, C, D, chunk=128, impl=None):
        def token(state, row):
            x_t, dt_t, b_t, c_t = row
            state = bf16(jnp.exp(dt_t[..., None] * A) * state
                         + (dt_t * x_t)[..., None] * b_t[:, None, :])
            return state, jnp.sum(state * c_t[:, None, :], axis=-1) + D * x_t

        # a chunk's states are made again for its backward, as the kernels
        # do: 16,384 of them a layer are 5.4 GB
        b, length, e = x.shape
        size = chunk if length % chunk == 0 else length
        rows = tuple(jnp.moveaxis(t.astype(jnp.float32), 1, 0)
                     .reshape(length // size, size, b, -1)
                     for t in (x, dt, B, C))
        zero = jnp.zeros((b,) + A.shape, jnp.float32)
        _, y = jax.lax.scan(jax.checkpoint(
            lambda state, block: jax.lax.scan(token, state, block)), zero, rows)
        return jnp.moveaxis(y.reshape(length, b, e), 0, 1).astype(x.dtype)
    return rounded_state


def _check_cell(args, jamba):
    """The job's own ``check_against_reference`` on the cell's own build
    (its parameters laid out as the family lays them, its sample, its mesh),
    once a (seed, variant)."""
    import math

    from benchmark import harness
    from benchmark.jobs import train as job
    cell = harness.load_cell(args.cell, os.path.abspath(args.root))
    family = cell.load_module("families", cell.config["family"])
    traffic = cell.traffic
    global_batch = (traffic["micro_batch"] * traffic["accumulation"]
                    * math.prod(traffic["mesh"].values()))
    real = jamba.selective_scan
    for seed in args.seeds:
        built = family.build(cell.config, traffic, seed, global_batch)
        for variant in args.variants:
            jamba.selective_scan = _lowered(jamba, variant)
            t0 = time.perf_counter()
            try:
                facts = job.check_against_reference(cell, built)
            finally:
                jamba.selective_scan = real
            print(json.dumps(dict(
                facts, cell=cell.name, seed=seed, variant=variant,
                seconds=time.perf_counter() - t0)), flush=True)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="checkout to import from")
    parser.add_argument("--cell", default="", help="a cell of BENCHMARK.json: "
                        "the job's own check on its own build and chips")
    parser.add_argument("--layers", type=int, default=4)
    parser.add_argument("--positions", type=int, default=4096)
    parser.add_argument("--seeds", type=int, nargs="+", default=[7])
    parser.add_argument("--variants", nargs="+", default=list(VARIANTS),
                        choices=VARIANTS)
    parser.add_argument("--precise-layers", type=int, default=None)
    args = parser.parse_args(argv)

    sys.path.insert(0, os.path.abspath(args.root))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from autodist_tpu.models import jamba
    from benchmark.families.common import zipf_tokens
    from benchmark.reference import jamba as reference

    if args.precise_layers is not None:
        jamba.PRECISE_LAYERS = args.precise_layers
    if args.cell:
        return _check_cell(args, jamba)
    on_chip = jax.default_backend() == "tpu"
    cfg = jamba.JambaConfig(
        n_layers=args.layers, attn_period=args.layers,
        attn_offset=args.layers // 2, dtype=jnp.bfloat16,
        attention_impl="flash" if on_chip else "dot",
        ssm_impl="pallas" if on_chip else "xla", fused_head=on_chip)
    kinds = dict(n_layers=cfg.n_layers, attn_period=cfg.attn_period,
                 attn_offset=cfg.attn_offset, d_state=cfg.d_state,
                 dt_rank=cfg.dt_rank, n_heads=cfg.n_heads,
                 n_kv_heads=cfg.n_kv_heads, rms_eps=cfg.rms_eps)
    real = jamba.selective_scan
    for seed in args.seeds:
        model, params = jamba.init_params(cfg, rng=jax.random.PRNGKey(seed))
        batch = {"tokens": jnp.asarray(zipf_tokens(
            np.random.default_rng(seed + 1), (1, args.positions + 1),
            cfg.vocab_size))}
        with jax.default_matmul_precision("highest"):
            want_loss, want = jax.jit(jax.value_and_grad(
                lambda p: reference.loss(p, batch, **kinds)))(params)
        norm = sum(float(jnp.sum(jnp.square(x)))
                   for x in jax.tree_util.tree_leaves(want))
        for variant in args.variants:
            jamba.selective_scan = _lowered(jamba, variant)
            t0 = time.perf_counter()
            try:
                loss, grads = jax.jit(jax.value_and_grad(
                    jamba.make_loss_fn(model)))(params, batch)
            finally:
                jamba.selective_scan = real
            distance = sum(float(jnp.sum(jnp.square(a - b))) for a, b in zip(
                jax.tree_util.tree_leaves(grads),
                jax.tree_util.tree_leaves(want)))
            print(json.dumps({
                "device": jax.devices()[0].device_kind, "layers": args.layers,
                "positions": args.positions, "seed": seed, "variant": variant,
                "loss_rel_diff": abs(float(loss) - float(want_loss))
                / float(want_loss),
                "grad_rel_l2": (distance / norm) ** 0.5,
                "seconds": time.perf_counter() - t0}), flush=True)


if __name__ == "__main__":
    main()
