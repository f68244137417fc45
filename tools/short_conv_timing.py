#!/usr/bin/env python3
"""Time the gated short convolution alone, on the chip: the two Pallas
kernels of ``ops/short_conv.py`` against XLA's lowering of the plain path.

The instrument behind ``FWD_BLOCK_ROWS`` / ``BWD_BLOCK_ROWS`` / ``_CHANNELS``.
At ``--shape B,L,d`` (default the LFM2 cell's 2,8192,2048, taps ``--k`` 3,
bfloat16 ``bcu``) it runs, under the profiler, the forward and the backward
of each path as one jitted call (the backward on ``bcu``, ``w`` and ``dy``,
which is all the custom VJP keeps) and prints one JSON line a measurement:
``device_ms`` = every device operation of a call summed (the plain path is
several fusions, the Pallas path its kernel and the taps' transpose),
``kernel_ms`` = the Pallas kernel's own events (median), and ``least_ms`` =
what the chip's memory bandwidth allows for the operator's bytes (8 B an
element forward, 14 backward: every operand once), with the share of it.

    python tools/short_conv_timing.py
    python tools/short_conv_timing.py --blocks 128,512 --blocks 512,256
    python tools/short_conv_timing.py --check    # the two paths' values, on the chip

``--blocks rows,channels`` overrides the kernels' row block and the channels
a walk step takes (may repeat: a sweep). ``--check`` compares y, dbcu and dw
of the two paths at the shape and exits 1 where they differ. Needs the TPU.
"""

import argparse
import json
import os
import sys
import tempfile
import time

from flash_forward_timing import kernel_ms   # device events of a trace by name

HBM_BYTES_PER_S = 819e9         # TPU v5e, benchmark/peaks.py
# relative L2 distance up to which the kernels agree with the plain path: both
# compute in float32 and round the result once to bfloat16; dw is a float32 sum
# of T products in another order
CHECK_TOLERANCE = {"y": 1e-2, "dbcu": 1e-2, "dw": 1e-3}


def build(sc, direction: str, impl: str, blocks, operands):
    import jax
    bcu, w, dy = operands
    rows, channels = blocks or (None, None)
    if impl == "xla":
        if direction == "fwd":
            return jax.jit(sc._plain), (bcu, w)
        return jax.jit(lambda bcu, w, dy: jax.vjp(sc._plain, bcu, w)[1](dy)), \
            (bcu, w, dy)
    if direction == "fwd":
        return jax.jit(lambda bcu, w: sc._forward_call(
            bcu, w, False, block_rows=rows, channels=channels)), (bcu, w)
    return jax.jit(lambda bcu, w, dy: sc._backward_call(
        bcu, w, dy, False, block_rows=rows, channels=channels)), (bcu, w, dy)


def measure(sc, direction, impl, blocks, operands, calls):
    import jax
    fn, args = build(sc, direction, impl, blocks, operands)
    try:
        jax.block_until_ready(fn(*args))
    except Exception as e:  # noqa: BLE001 — a sweep goes on past refused tiles
        return {"direction": direction, "impl": impl, "blocks": blocks,
                "refused": str(e).splitlines()[0][:300]}
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            t0 = time.perf_counter()
            jax.block_until_ready([fn(*args) for _ in range(calls)])
            host = (time.perf_counter() - t0) / calls * 1e3
        every = kernel_ms(trace_dir, "")       # every device operation
        kernel = sorted(kernel_ms(trace_dir, f"short_conv_{direction}"))
    elements = operands[2].size
    least = elements * (8 if direction == "fwd" else 14) / HBM_BYTES_PER_S * 1e3
    record = {"direction": direction, "impl": impl, "blocks": blocks,
              "device_ms": sum(every) / calls, "call_ms_host": host,
              "least_ms": least, "share_pct": 100 * least / (sum(every) / calls)}
    if impl == "pallas":
        if not kernel:
            raise SystemExit(f"the trace holds no short_conv_{direction} event")
        record["kernel_ms"] = kernel[len(kernel) // 2]
        record["kernel_share_pct"] = 100 * least / record["kernel_ms"]
    return record


def check(sc, operands):
    import jax
    import jax.numpy as jnp
    bcu, w, dy = operands

    def both(impl):
        def run(bcu, w, dy):
            y, vjp = jax.vjp(lambda bcu, w: sc.gated_short_conv(bcu, w, impl),
                             bcu, w)
            return (y, *vjp(dy))
        return jax.jit(run)(bcu, w, dy)

    got, want = both("pallas"), both("xla")

    def distance(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    record = {"check": True, **{part: distance(a, b) for part, a, b
                                in zip(CHECK_TOLERANCE, got, want)}}
    record["agree"] = all(record[part] <= limit     # a NaN agrees with nothing
                          for part, limit in CHECK_TOLERANCE.items())
    return record


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="checkout to import autodist_tpu from")
    parser.add_argument("--shape", default="2,8192,2048", help="B,L,d")
    parser.add_argument("--k", type=int, default=3)
    parser.add_argument("--blocks", action="append", default=[],
                        help="rows,channels override of both kernels; may repeat")
    parser.add_argument("--check", action="store_true")
    parser.add_argument("--calls", type=int, default=20)
    args = parser.parse_args(argv)

    sys.path.insert(0, os.path.abspath(args.root))
    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu":
        raise SystemExit(f"needs the TPU, the backend is {jax.default_backend()!r}")
    from autodist_tpu.ops import short_conv as sc

    batch, length, d = (int(x) for x in args.shape.split(","))
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    operands = (jax.random.normal(keys[0], (batch, length, 3 * d), jnp.bfloat16),
                jax.random.normal(keys[1], (d, args.k), jnp.float32),
                jax.random.normal(keys[2], (batch, length, d), jnp.bfloat16))

    def emit(record):
        print(json.dumps({"shape": args.shape, "k": args.k, **record}), flush=True)

    if args.check:
        record = check(sc, operands)
        emit(record)
        if not record["agree"]:
            raise SystemExit(1)
        return
    sweeps = [tuple(int(x) for x in b.split(",")) for b in args.blocks] or [None]
    for direction in ("fwd", "bwd"):
        emit(measure(sc, direction, "xla", None, operands, args.calls))
        for blocks in sweeps:
            emit(measure(sc, direction, "pallas", blocks, operands, args.calls))


if __name__ == "__main__":
    main()
