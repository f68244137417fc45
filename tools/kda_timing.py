#!/usr/bin/env python3
"""Time Kimi Delta Attention's recurrence alone, on the chip: the two Pallas
kernels of ``ops/kda_scan.py`` at ``--shape B,L,H,D`` (default
``ling-pretrain-8k``'s 1,8192,16,128, ``--chunk`` 64, bfloat16 operands, a
float32 log-decay), each as one jitted call under the profiler. One JSON line
a measurement: ``device_ms`` = every device operation of a call summed (the
kernel with what XLA lays out around it: ``beta`` turned to rows),
``kda_fwd_ms`` / ``kda_bwd_ms`` = the kernel's own events (median) and
``least_ms`` = the larger of the part's products at the chip's bf16 peak and
its bytes at the memory bandwidth (``benchmark/flops_bailing_hybrid.py``
``kda_cost``) with the share of it.

    python tools/kda_timing.py
    python tools/kda_timing.py --check   # kernels against the recurrence, on the chip

``--check`` compares ``o``, the five gradients and the last state of the
kernels (a) on float32 operands with the token-at-a-time recurrence in
float32 (the products' precision on the chip: the solve and the running sums
must hold float32) and (b) on bfloat16 operands with the plain form on the
same operands, at three chunks and a ragged length, and exits 1 where they
differ. Needs the TPU.
"""

import argparse
import json
import os
import sys

from eva_timing import timed      # one jitted call under the profiler

# relative L2 distance up to which the kernels agree: float32 operands with
# the recurrence; bfloat16 operands with the plain form of the same chunks
# (both round the same operands where they enter a product)
CHECK_F32, CHECK_BF16 = 1e-4, 2e-2
NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta", "state")


def operands(shape, dtype, seed=0):
    """Normalised q and k, a log-decay over all of (-5, 0), beta in (0, 1),
    and the weights of the check's scalar."""
    import jax
    import jax.numpy as jnp
    b, length, h, d = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v, w = (jax.random.normal(key, shape) for key in keys[:4])
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    g = -5.0 * jax.nn.sigmoid(3.0 * jax.random.normal(keys[4], shape))
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (b, length, h)))
    return ((unit(q) * d ** -0.5).astype(dtype), unit(k).astype(dtype),
            v.astype(dtype), g, beta), w


def check(ks, length, heads, depth, chunk):
    import jax
    import jax.numpy as jnp

    from benchmark.reference.bailing_hybrid import delta_rule

    def recurrence(*inputs):    # the recurrence itself, a token at a time
        return delta_rule(*(x.astype(jnp.float32) for x in inputs))

    def all_of(run, last, inputs, w):
        def loss(*inputs):
            out = run(*inputs).astype(jnp.float32)
            return jnp.sum(out * w), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*inputs)
        return (out, *grads, jax.jit(last)(*inputs))

    def distance(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    form = lambda impl: (  # noqa: E731
        lambda *x: ks.kda_scan(*x, chunk=chunk, impl=impl),
        lambda *x: ks.kda_last_state(*x, chunk=chunk, impl=impl))
    record, agree = {"check": True, "length": length}, True
    for dtype, other, tolerance in (
            ("float32", (lambda *x: recurrence(*x)[0],
                         lambda *x: recurrence(*x)[1]), CHECK_F32),
            ("bfloat16", form("xla"), CHECK_BF16)):
        inputs, w = operands((1, length, heads, depth), jnp.dtype(dtype))
        with jax.default_matmul_precision("highest"):
            want = all_of(*other, inputs, w)
        got = all_of(*form("pallas"), inputs, w)
        for name, a, b in zip(NAMES, got, want):
            record[f"{dtype}.{name}"] = distance(a, b)
            agree = agree and record[f"{dtype}.{name}"] <= tolerance   # NaN agrees with nothing
    record["agree"] = agree
    return record


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    parser.add_argument("--root", default=root,
                        help="checkout to import autodist_tpu from")
    parser.add_argument("--shape", default="1,8192,16,128", help="B,L,H,D")
    parser.add_argument("--chunk", type=int, default=64)
    parser.add_argument("--check", action="store_true")
    parser.add_argument("--calls", type=int, default=5)
    args = parser.parse_args(argv)

    sys.path.insert(0, os.path.abspath(args.root))
    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu":
        raise SystemExit(f"needs the TPU, the backend is {jax.default_backend()!r}")
    from autodist_tpu.ops import kda_scan as ks
    from benchmark import flops_bailing_hybrid, peaks

    b, length, h, d = (int(x) for x in args.shape.split(","))

    def emit(record):
        print(json.dumps({"shape": args.shape, "chunk": args.chunk, **record}),
              flush=True)

    if args.check:
        agree = True
        for n in (args.chunk, 3 * args.chunk, 3 * args.chunk + 36):
            record = check(ks, n, min(h, 2), d, args.chunk)
            emit(record)
            agree = agree and record["agree"]
        if not agree:
            raise SystemExit(1)
        return
    (q, k, v, g, beta), w = operands((b, length, h, d), jnp.bfloat16)
    rows = lambda x: x.reshape(b, length, h * d)  # noqa: E731
    q, k, v, g, do = (rows(x) for x in (q, k, v, g, w.astype(jnp.bfloat16)))
    chip = peaks.peaks_for(jax.devices()[0].device_kind)
    least = dict(zip(("fwd", "bwd"), flops_bailing_hybrid.kda_cost(
        batch=b, seq_len=length, heads=h, head_dim=d, chunk=args.chunk)))
    forward = jax.jit(lambda *x: ks._forward_call(*x, args.chunk, False))
    _, states, _ = forward(q, k, v, g, beta)
    for part, fn, inputs in (
            ("fwd", forward, (q, k, v, g, beta)),
            ("bwd", jax.jit(lambda *x: ks._backward_call(*x, args.chunk, False)),
             (q, k, v, g, beta, states, do))):
        record = {"part": part, **timed(fn, inputs, args.calls, (f"kda_{part}",))}
        record["least_ms"] = least[part].least_seconds(chip) * 1e3
        record["bound"] = least[part].bound(chip)
        record["kernel_share_pct"] = 100 * record["least_ms"] \
            / record[f"kda_{part}_ms"]
        emit(record)


if __name__ == "__main__":
    main()
