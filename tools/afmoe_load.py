#!/usr/bin/env python3
"""How unevenly the sigmoid router loads its experts while a cell trains, on
the chip: what sets ``rows_bound`` of one chip's share (``models/moe.py``
``routed_experts``).

    python tools/afmoe_load.py --cell trinity-pretrain-8k --seeds 1,2 --rates 3e-4 --steps 120

Trains the cell's own model from the cell's own seeded weights and batch pool
with the cell's optimizer (``benchmark/families/afmoe.py``), one jitted step,
and reads every expert layer's load over the router's full width from the
``load`` the layer sows. One JSON line a step: the loss, and per expert layer
the rows of the experts held here and their share of ``rows_bound`` (what
of a pass's buffer the row kernel of ``ops/moe_rows.py`` moves: it fetches no
row past the held ones), the passes they took (``passes``: the
first keeps what its backward reads, each one more is computed twice), the
rows of the fullest of the ``width / held`` ranks a deployment would have
(any of them could be this chip), and of the fullest expert. ``--rows-bound``
overrides the rows a pass computes (default tokens x top_k / 2: one pass
nearly always, so that the step's time does not follow the loads it reports).
Needs the TPU at the cell's size.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cell", default="trinity-pretrain-8k")
    parser.add_argument("--root", default=ROOT,
                        help="the checkout whose BENCHMARK.json names the cell")
    parser.add_argument("--seeds", default="1")
    parser.add_argument("--rates", default="", help="learning rates; default: the cell's")
    parser.add_argument("--steps", type=int, default=120)
    parser.add_argument("--rows-bound", type=int, default=None)
    parser.add_argument("--every", type=int, default=1, help="print every n-th step")
    parser.add_argument("--warmups", default="",
                        help="warm-up steps to try; default: the cell's")
    parser.add_argument("--balance", type=int, default=None,
                        help="iterations of the balancing rule before step 1 "
                             "(0: none); default: the cell's")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import optax

    from autodist_tpu.models import afmoe, moe
    from autodist_tpu.models.common import fused_lm_head_nll
    from benchmark import harness

    cell = harness.load_cell(args.cell, args.root)
    family = cell.load_module("families", cell.config["family"])
    traffic = cell.traffic
    tokens = traffic["micro_batch"] * traffic["seq_len"]
    config = dict(cell.config)
    bound = args.rows_bound or tokens * config["num_experts_per_tok"] // 2
    config["assumed"] = dict(config["assumed"], rows_bound=bound)
    cfg = family.model_config(config)
    model = afmoe.Afmoe(cfg)
    held, width = cfg.experts_held, cfg.n_experts_routed

    def loss_fn(params, batch):
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
        (h, bias_term), sown = model.apply({"params": params}, inputs,
                                           return_hidden=True,
                                           mutable=["intermediates"])
        return (fused_lm_head_nll(h, params, targets).mean() + bias_term,
                (afmoe.sown_loads(sown["intermediates"]),
                 moe.sown_passes(sown["intermediates"])))

    assumed = config["assumed"]
    if args.balance is not None:
        assumed["expert_bias_balance"] = args.balance and dict(
            assumed.get("expert_bias_balance") or {"first_coeff": 0.05},
            iterations=args.balance)
    rates = [float(r) for r in args.rates.split(",") if r] or \
        [assumed["learning_rate"]]
    warmups = [int(w) for w in args.warmups.split(",") if w] or \
        [assumed.get("warmup_steps", 0)]
    for seed, rate, warmup in ((int(s), r, w) for s in args.seeds.split(",")
                               for r in rates for w in warmups):
        built = family.build(dict(config, assumed=dict(
            assumed, learning_rate=rate, warmup_steps=warmup)), traffic,
            seed, traffic["micro_batch"])
        optimizer = built.optimizer

        def step(params, opt_state, batch):
            (loss, sown), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss, sown

        step = jax.jit(step, donate_argnums=(0, 1))
        params, opt_state = built.params, optimizer.init(built.params)
        worst = 0
        for i in range(args.steps):
            batch = {k: jnp.asarray(v)
                     for k, v in built.pool[i % len(built.pool)].items()}
            params, opt_state, loss, sown = step(params, opt_state, batch)
            loads, passes = jax.device_get(sown)
            ranks = loads.reshape(loads.shape[0], width // held, held).sum(-1)
            worst = max(worst, int(ranks.max()))
            held_rows = ranks[:, cfg.first_expert_held // held]
            if i % args.every == 0 or i == args.steps - 1:
                print(json.dumps({
                    "seed": seed, "rate": rate, "warmup": warmup, "step": i + 1,
                    "loss": float(loss), "rows_bound": bound,
                    "held_rows": [int(x) for x in held_rows],
                    # the share of a pass's buffer the row kernel moves
                    # (ops/moe_rows.py fetches no row past the held ones)
                    "held_rows_over_bound": [round(float(x) / bound, 4)
                                             for x in held_rows],
                    "passes": [int(x) for x in passes],
                    "fullest_rank_rows": [int(x) for x in ranks.max(-1)],
                    "fullest_expert_rows": [int(x) for x in loads.max(-1)],
                    "fullest_rank_so_far": worst}), flush=True)


if __name__ == "__main__":
    main()
