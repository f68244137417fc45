#!/usr/bin/env python3
"""The benchmark's check of ``nemotron-pretrain-8k`` taken apart, on the chip:
the system's gradient against the plain reference's (``benchmark/reference/
nemotron_h.py``, float32 at the highest matmul precision) by parameter, for
the cell's configuration and for variants of it that take one source of
rounding away at a time (``tools/moe_timing.py --phases gradcheck`` does the
same for OLMoE). One JSON line a variant: the whole gradient's relative L2
distance, each parameter's own, and each parameter's share of the squared
distance (where the distance is made).

    python tools/nemotron_gradcheck.py [--seed 11] [--variants cell,xla-scan,...]

Needs the TPU at the cell's size (~4 min). ``--positions 512`` runs the cell's
configuration at its published widths on the CPU instead (``JAX_PLATFORMS=cpu``,
25 s a variant after a minute of set-up): that many positions, 4,096
vocabulary rows, the plain scan, dot attention and XLA's head, which ranks the
sources of rounding as the chip does (PERF.md section 6, "PR 35"); ``--root
<scratch root> --cell <tiny cell>`` rehearses the tool itself.
"""

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def variants(jnp):
    return {
        "cell": ({}, None),
        "bf16-first-layer": ({"exact_first_layer": False}, None),
        "xla-scan": ({"ssm_impl": "xla"}, None),
        "no-remat": ({"remat": False}, None),
        "xla-head": ({"fused_head": False}, None),
        "f32": ({"dtype": jnp.float32, "ssm_impl": "xla", "fused_head": False},
                "highest"),
        # one kind of mixer in float32 at the highest precision, the rest as
        # the cell has them (the mixer's class is swapped for the variant)
        "f32-mamba": ({"ssm_impl": "xla"}, None, "Mamba2"),
        "f32-experts": ({}, None, "RoutedShare"),
        "f32-attention": ({}, None, "GroupedAttention"),
        # the scan alone in float32 (its operands as the bfloat16 layer hands
        # them), and the Mamba-2 layer in float32 around a bfloat16 scan
        "f32-scan": ({"ssm_impl": "xla"}, None, None, jnp.float32),
        "f32-mamba-bf16-scan": ({"ssm_impl": "xla"}, None, "Mamba2", jnp.bfloat16),
    }


def scan_in(nemotron_h, dtype):
    """``nemotron_h.ssd_scan`` on operands cast to ``dtype``, at the highest
    matmul precision where that is float32; returns what puts it back."""
    import jax
    import jax.numpy as jnp
    original = nemotron_h.ssd_scan

    def scan(x, dt, A, B, C, D, **kwargs):
        precision = "highest" if dtype == jnp.float32 else "default"
        with jax.default_matmul_precision(precision):
            return original(x.astype(dtype), dt, A, B.astype(dtype),
                            C.astype(dtype), D, **kwargs).astype(x.dtype)

    nemotron_h.ssd_scan = scan
    return lambda: setattr(nemotron_h, "ssd_scan", original)


def in_float32(nemotron_h, mixer: str):
    """``nemotron_h.<mixer>`` built with a float32 configuration and applied
    at the highest matmul precision; returns what to call to put the class
    back."""
    import jax
    import jax.numpy as jnp
    original = getattr(nemotron_h, mixer)

    def build(config, *arguments, name):
        module = original(dataclasses.replace(config, dtype=jnp.float32),
                          *arguments, name=name)

        def call(h):
            with jax.default_matmul_precision("highest"):
                return module(h.astype(jnp.float32))
        return call

    setattr(nemotron_h, mixer, build)
    return lambda: setattr(nemotron_h, mixer, original)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--root", default=ROOT)
    parser.add_argument("--cell", default="nemotron-pretrain-8k")
    parser.add_argument("--seed", type=int, default=11)
    parser.add_argument("--variants", default="cell,xla-scan")
    parser.add_argument("--brief", action="store_true",
                        help="leave each parameter's own distance out")
    parser.add_argument("--positions", type=int,
                        help="the published widths at this length, on the CPU")
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp

    from autodist_tpu.models import nemotron_h
    from benchmark import harness
    cell = harness.load_cell(args.cell, args.root)
    if args.positions:
        held = (args.positions * cell.config["num_experts_per_tok"]
                * cell.config["n_routed_experts"] // cell.config["router_width"])
        cell.config.update(vocab_size=4096, assumed=dict(
            cell.config["assumed"], ssm_impl="xla", attention_impl="dot",
            fused_head=False, remat=False, rows_bound=2 * held,
            expert_bias_balance={"first_coeff": 0.05, "iterations": 16}))
        cell.traffic.update(seq_len=args.positions, pool_batches=2)
    family = cell.load_module("families", "nemotron_h")
    reference = cell.load_module("reference", "nemotron_h")
    built = family.build(cell.config, cell.traffic, args.seed,
                         cell.traffic["micro_batch"])
    sample = {k: jnp.asarray(v) for k, v in built.sample.items()}
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(jax.grad(lambda p, b: reference.loss(
            p, b, **built.reference_config)))(built.params, sample)
    cfg = family.model_config(cell.config)
    for name in args.variants.split(","):
        changes, precision, *swaps = variants(jnp)[name]
        mixer, scan_dtype = (swaps + [None, None])[:2]
        undo = [in_float32(nemotron_h, mixer)] if mixer else []
        if scan_dtype is not None:
            undo.append(scan_in(nemotron_h, scan_dtype))
        restore = lambda undo=undo: [put_back() for put_back in undo]  # noqa: E731
        loss_fn = nemotron_h.make_loss_fn(
            nemotron_h.NemotronH(dataclasses.replace(cfg, **changes)))
        with jax.default_matmul_precision(precision or "default"):
            grads = jax.jit(jax.grad(loss_fn))(built.params, sample)
        restore()
        rows = {jax.tree_util.keystr(path): (float(jnp.sum(jnp.square(g - r))),
                                             float(jnp.sum(jnp.square(r))))
                for (path, g), r in zip(
                    jax.tree_util.tree_leaves_with_path(grads),
                    jax.tree_util.tree_leaves(ref))}
        diff, norm = (sum(x) for x in zip(*rows.values()))
        print(json.dumps({
            "variant": name, "seed": args.seed,
            "grad_rel_l2": (diff / norm) ** 0.5,
            "rel_l2_by_parameter": {} if args.brief else {
                k: round((d / n) ** 0.5, 4)
                for k, (d, n) in rows.items() if n > 0},
            "share_of_squared_distance_pct": {
                k: round(100 * d / diff, 2) for k, (d, _) in rows.items()
                if d > 0.005 * diff}}), flush=True)
        del grads


if __name__ == "__main__":
    main()
