"""Train a bigger decoder of the flagship's kind on one chip (the round-5
scaling rows; README, "Round-5 figures", keeps the command and no table).

    PYTHONPATH=. python examples/scale_lm.py --d_model 768 --n_layers 12 --batch_size 192
    PYTHONPATH=. python examples/scale_lm.py --d_model 1024 --n_layers 12 --batch_size 128
    PYTHONPATH=. python examples/scale_lm.py --d_model 1024 --n_layers 24 --batch_size 96

Same framework and step as the flagship bench (AllReduce, bf16, fused pallas
head, XLA attention at seq 256), just a bigger decoder: MFU rises with model
size as the matmuls grow (48% at 52M -> ~59-60% at 217M on a v5e). The fused-head
kernels fit their tile sizes to VMEM automatically, which is what makes
d_model >= 768 with f32 tables work at all (ops/fused_xent.py).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import optax

from autodist_tpu import AutoDist
from autodist_tpu.models import transformer_lm
from autodist_tpu.ops import mosaic_compiles
from autodist_tpu.strategy import AllReduce
from autodist_tpu.utils import flops as flops_util


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--d_model", type=int, default=768)
    parser.add_argument("--n_layers", type=int, default=12)
    parser.add_argument("--batch_size", type=int, default=192)
    parser.add_argument("--seq_len", type=int, default=256)
    parser.add_argument("--vocab", type=int, default=32_000)
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--remat", action="store_true")
    args = parser.parse_args(argv)

    on_accel = jax.default_backend() != "cpu"
    cfg = transformer_lm.TransformerLMConfig(
        vocab_size=args.vocab, d_model=args.d_model,
        n_heads=max(1, args.d_model // 64), n_layers=args.n_layers,
        d_ff=4 * args.d_model, max_len=args.seq_len,
        dtype=jnp.bfloat16 if on_accel else jnp.float32, tied_output=False,
        remat=args.remat, fused_head=mosaic_compiles())

    model, params = transformer_lm.init_params(cfg)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    loss_fn = transformer_lm.make_loss_fn(model)
    batch = transformer_lm.synthetic_batch(cfg, batch_size=args.batch_size,
                                           seq_len=args.seq_len)

    ad = AutoDist(strategy_builder=AllReduce())
    step = ad.function(loss_fn, params, optax.adam(1e-3), example_batch=batch)
    batch = step.runner.shard_batch(batch)

    for _ in range(2):
        loss = step(batch)
    _ = float(loss)  # compile + pipeline fence
    t0 = time.perf_counter()
    for _ in range(args.steps):
        loss = step(batch)
    _ = float(loss)
    dt = time.perf_counter() - t0

    tokens_per_step = args.batch_size * args.seq_len
    rate = tokens_per_step * args.steps / dt
    fpt = flops_util.transformer_flops_per_token(
        cfg.d_model, cfg.n_layers, cfg.d_ff, cfg.vocab_size, args.seq_len)
    print(f"d{cfg.d_model}x{cfg.n_layers} bs{args.batch_size} "
          f"seq{args.seq_len} ({n_params / 1e6:.0f}M params): "
          f"final loss {float(loss):.4f}, {rate:,.0f} tokens/sec")
    flops_util.report_mfu(fpt * tokens_per_step / len(jax.devices()),
                          rate / tokens_per_step)
    return rate


if __name__ == "__main__":
    main()
