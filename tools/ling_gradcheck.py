#!/usr/bin/env python3
"""Take ``ling-pretrain-8k``'s check apart: the whole gradient's distance to
the plain reference's (``benchmark/jobs/train.py`` ``check_against_reference``:
``grad_rel_l2``, limit 0.05) by parameter, with one source of rounding taken
away at a time. On the chip at the cell's size (``--positions 8192``, ~3 min +
1 a variant), or on the CPU at the published widths and fewer positions and
layers (``JAX_PLATFORMS=cpu ... --positions 512 --layers kda,kda,mla --vocab
2048``: the plain form of the same chunks on the same bfloat16 operands, which
on the chip is the kernels' arithmetic to the bit).

    PYTHONPATH=. python tools/ling_gradcheck.py --seed 1 --variants cell,no-precise-layer

Variants (``models/bailing_hybrid.py``'s module constant, set here and nowhere
else): ``cell`` as it stands; ``f32`` every sublayer in float32 (what is left
is the form's, not rounding); ``no-precise-layer``: ``PRECISE_LAYERS`` 0, the
first layer's ordinary bfloat16 forward as the stream (0.084-0.092 on the chip
where the cell reads under 0.05: must fail).
"""

import argparse
import dataclasses
import json
import os
import sys


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    parser.add_argument("--root", default=root)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--positions", type=int, default=8192)
    parser.add_argument("--layers", default="", help="kda,kda,mla: another stack")
    parser.add_argument("--vocab", type=int, default=0)
    parser.add_argument("--dense", type=int, default=None,
                        help="first_k_dense_replace: that many leading dense layers")
    parser.add_argument("--variants", default="cell")
    parser.add_argument("--top", type=int, default=12)
    args = parser.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.root))

    import jax
    import jax.numpy as jnp

    from autodist_tpu.models import bailing_hybrid as bh
    from benchmark import harness

    cell = harness.load_cell("ling-pretrain-8k", args.root)
    config = dict(cell.config)
    if args.layers:
        kinds = args.layers.split(",")
        config.update(layer_types=kinds, num_hidden_layers=len(kinds))
    if args.vocab:
        config["vocab_size"] = args.vocab
    if args.dense is not None:
        config["first_k_dense_replace"] = args.dense
    if jax.default_backend() == "cpu":
        config["assumed"] = dict(config["assumed"], kda_impl="xla",
                                 attention_impl="dot", fused_head=False,
                                 expert_bias_balance=None)
    traffic = dict(cell.traffic, seq_len=args.positions, pool_batches=1)
    family = cell.load_module("families", "bailing_hybrid")
    if args.layers:     # the family holds layer_types to the model's own rule
        check = family.model_config

        def relaxed(config):
            kinds = config["layer_types"]
            rule = dict(config, layer_types=[
                "mla" if (i + 1) % config["layer_group_size"] == 0 else "kda"
                for i in range(len(kinds))])
            return dataclasses.replace(check(rule), layer_types=tuple(kinds))
        family.model_config = relaxed
    reference = cell.load_module("reference", "bailing_hybrid")

    def distance(built):
        sample = {k: jnp.asarray(v) for k, v in built.sample.items()}
        loss, grads = jax.jit(jax.value_and_grad(built.loss_fn))(
            built.params, sample)
        with jax.default_matmul_precision("highest"):
            want, want_grads = jax.jit(jax.value_and_grad(
                lambda p, b: reference.loss(p, b, **built.reference_config)))(
                    built.params, sample)
        rows = []
        for (path, got), ref in zip(
                jax.tree_util.tree_leaves_with_path(grads),
                jax.tree_util.tree_leaves(want_grads)):
            rows.append((jax.tree_util.keystr(path),
                         float(jnp.sum(jnp.square(got - ref))),
                         float(jnp.sum(jnp.square(ref)))))
        norm = sum(r[2] for r in rows)
        return {"loss_rel_diff": abs(float(loss) - float(want)) / abs(float(want)),
                "grad_rel_l2": (sum(r[1] for r in rows) / norm) ** 0.5,
                "by_parameter": [
                    {"leaf": name, "share_of_error_pct": 100 * err / sum(
                        r[1] for r in rows), "own_rel_l2": (err / own) ** 0.5
                     if own else None}
                    for name, err, own in sorted(rows, key=lambda r: -r[1])[
                        :args.top]]}

    for variant in args.variants.split(","):
        switches = VARIANTS[variant]
        saved = {name: getattr(bh, name) for name in switches}
        assumed = dict(config["assumed"])
        if variant == "f32":
            assumed["activation_dtype"] = "float32"
        try:
            for name, value in switches.items():
                setattr(bh, name, value)
            built = family.build(dict(config, assumed=assumed), traffic,
                                 args.seed, 1)
            record = distance(built)
        finally:
            for name, value in saved.items():
                setattr(bh, name, value)
        print(json.dumps({"variant": variant, "seed": args.seed,
                          "positions": args.positions, **record}), flush=True)


VARIANTS = {
    "cell": {},
    "f32": {},
    # no layer computed a second time: the ordinary bfloat16 forward's stream
    "no-precise-layer": {"PRECISE_LAYERS": 0},
    "precise-1": {"PRECISE_LAYERS": 1},
    "precise-3": {"PRECISE_LAYERS": 3},
}


if __name__ == "__main__":
    main()
