#!/usr/bin/env python3
"""Time EVA attention alone, on the chip: the two Pallas kernels of
``ops/eva_attention.py``, the pooling XLA keeps, and the OTHER form of the
operator (the existing flash kernels over the two key sets, joined by their
log-sum-exp) for what its kernels alone would cost.

At ``--shape B,L,H,D`` (default ``evabyte-pretrain-16k``'s 1,16384,8,128,
``--window`` 2048, ``--chunk`` 16, bfloat16) it runs, under the profiler, each
part as one jitted call and prints one JSON line a measurement: ``device_ms``
= every device operation of a call summed (a kernel with what XLA lays out
around it), ``kernel_ms`` = the Pallas kernel's own events (median) and
``least_ms`` = the larger of the part's products at the chip's bf16 peak and
its bytes at the memory bandwidth (``benchmark/flops_evabyte.py`` ``eva_cost``)
with the share of it:

* ``pool`` / ``pool-bwd``: the summaries from k and v, and their transpose;
* ``fwd`` / ``bwd``: ``eva_fwd``, and ``eva_bwd`` on the forward's residuals;
* ``flash-windows``: ``flash_attention`` causal over the windows folded into
  the batch (the in-window half of the joined form), forward + backward;
* ``flash-summaries``: ``flash_attention`` non-causal, window ``w``'s queries
  over the ``w * window / chunk`` summaries before it, one call a window,
  forward + backward (the joined form's other half; its log-sum-exp join and
  the backward's hand-over of the joint ``o`` / lse are elementwise work not
  timed here).

    python tools/eva_timing.py
    python tools/eva_timing.py --check     # kernels against the dot form, on the chip

``--check`` compares ``o`` and the five gradients of the two forms at three
windows and exits 1 where they differ. Needs the TPU.
"""

import argparse
import json
import os
import sys
import tempfile
import time

from flash_forward_timing import kernel_ms   # device events of a trace by name

# relative L2 distance up to which the kernels agree with the dot form on
# bfloat16 operands: both round p (and ds) once more where it enters a product
CHECK_TOLERANCE = 2e-2


def timed(fn, args, calls, kernels=()):
    import jax
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            t0 = time.perf_counter()
            jax.block_until_ready([fn(*args) for _ in range(calls)])
            host = (time.perf_counter() - t0) / calls * 1e3
        record = {"device_ms": sum(kernel_ms(trace_dir, "")) / calls,
                  "call_ms_host": host}
        for name in kernels:
            events = sorted(kernel_ms(trace_dir, name))
            if not events:
                raise SystemExit(f"the trace holds no {name} event")
            record[f"{name}_ms"] = events[len(events) // 2]
            record[f"{name}_calls"] = len(events) / calls
    return record


def parts(ea, fa, operands, window, chunk):
    """name -> (jitted function, arguments, kernel names to read)."""
    import jax
    import jax.numpy as jnp
    q, k, v, phi, mu, g = operands
    b, length, h, d = q.shape
    ks, vs = jax.jit(lambda *x: ea.eva_pool(*x, chunk))(k, v, phi, mu)
    o, lse = jax.jit(lambda *x: ea._forward(*x, window, chunk, False))(
        q, k, v, ks, vs)
    fold = lambda x: x.reshape(b * length // window, window, h, d)  # noqa: E731

    def flash_both(causal):
        def run(q, k, v, g):
            out, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
                q, k, v, causal=causal), q, k, v)
            return (out, *vjp(g))
        return run

    def summaries(q, ks, vs, g):
        per = window // chunk
        return [flash_both(False)(q[:, w * window:(w + 1) * window],
                                  ks[:, :w * per], vs[:, :w * per],
                                  g[:, w * window:(w + 1) * window])
                for w in range(1, length // window)]

    pool = lambda k, v, phi, mu: ea.eva_pool(k, v, phi, mu, chunk)  # noqa: E731
    return {
        "pool": (jax.jit(pool), (k, v, phi, mu), ()),
        "pool-bwd": (jax.jit(lambda k, v, phi, mu, dks, dvs: jax.vjp(
            pool, k, v, phi, mu)[1]((dks, dvs))),
            (k, v, phi, mu, jnp.ones_like(ks), jnp.ones_like(vs)), ()),
        "fwd": (jax.jit(lambda *x: ea._forward(*x, window, chunk, False)),
                (q, k, v, ks, vs), ("eva_fwd",)),
        "bwd": (jax.jit(lambda *x: ea._backward(*x, window, chunk, False)),
                (q, k, v, ks, vs, o, lse, g), ("eva_bwd",)),
        "flash-windows": (jax.jit(flash_both(True)),
                          tuple(fold(x) for x in (q, k, v, g)),
                          ("flash_fwd", "flash_bwd_dkv")),
        "flash-summaries": (jax.jit(summaries), (q, ks, vs, g),
                            ("flash_fwd", "flash_bwd_dkv")),
    }


def check(ea, window, chunk, heads, depth):
    import jax
    import jax.numpy as jnp
    keys = jax.random.split(jax.random.PRNGKey(1), 6)
    shape = (1, 3 * window, heads, depth)
    q, k, v, w = (jax.random.normal(key, shape, jnp.bfloat16) for key in keys[:4])
    phi, mu = (jax.random.normal(key, (heads, depth)) for key in keys[4:])

    def both(impl):
        def loss(q, k, v, phi, mu):
            out = ea.eva_attention(q, k, v, phi, mu, window=window, chunk=chunk,
                                   impl=impl).astype(jnp.float32)
            return jnp.sum(out * w.astype(jnp.float32)), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(q, k, v, phi, mu)
        return (out, *grads)

    def distance(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    record = {"check": True, **{name: distance(a, b) for name, a, b in zip(
        ("o", "dq", "dk", "dv", "dphi", "dmu"), both("kernel"), both("dot"))}}
    record["agree"] = all(record[name] <= CHECK_TOLERANCE    # a NaN agrees with nothing
                          for name in ("o", "dq", "dk", "dv", "dphi", "dmu"))
    return record


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    parser.add_argument("--root", default=root,
                        help="checkout to import autodist_tpu from")
    parser.add_argument("--shape", default="1,16384,8,128", help="B,L,H,D")
    parser.add_argument("--window", type=int, default=2048)
    parser.add_argument("--chunk", type=int, default=16)
    parser.add_argument("--parts", default="pool,pool-bwd,fwd,bwd,"
                        "flash-windows,flash-summaries")
    parser.add_argument("--check", action="store_true")
    parser.add_argument("--calls", type=int, default=10)
    args = parser.parse_args(argv)

    sys.path.insert(0, os.path.abspath(args.root))
    import importlib

    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu":
        raise SystemExit(f"needs the TPU, the backend is {jax.default_backend()!r}")
    from autodist_tpu.ops import eva_attention as ea
    from benchmark import flops_evabyte, peaks
    fa = importlib.import_module("autodist_tpu.ops.flash_attention")

    b, length, h, d = (int(x) for x in args.shape.split(","))

    def emit(record):
        print(json.dumps({"shape": args.shape, "window": args.window,
                          "chunk": args.chunk, **record}), flush=True)

    if args.check:
        record = check(ea, args.window, args.chunk, h, d)
        emit(record)
        if not record["agree"]:
            raise SystemExit(1)
        return
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    q, k, v, g = (jax.random.normal(key, (b, length, h, d), jnp.bfloat16)
                  for key in keys[:4])
    phi, mu = (jax.random.normal(key, (h, d)) * d ** -0.5 for key in keys[4:])
    chip = peaks.peaks_for(jax.devices()[0].device_kind)
    least = dict(zip(("fwd", "bwd"), flops_evabyte.eva_cost(
        batch=b, seq_len=length, heads=h, head_dim=d, window=args.window,
        chunk=args.chunk)))
    built = parts(ea, fa, (q, k, v, phi, mu, g), args.window, args.chunk)
    for name in args.parts.split(","):
        fn, operands, kernels = built[name]
        record = {"part": name, **timed(fn, operands, args.calls, kernels)}
        if name in least:
            record["least_ms"] = least[name].least_seconds(chip) * 1e3
            record["kernel_share_pct"] = 100 * record["least_ms"] \
                / record[f"{kernels[0]}_ms"]
        emit(record)


if __name__ == "__main__":
    main()
