#!/usr/bin/env python3
"""Time the flash forward kernel alone, on the chip, at named shapes.

The instrument behind the block choice in ``ops/flash_attention.py``
(``_forward_blocks``): one jitted ``_flash_forward`` (or one carry step) per
shape, run under the profiler, and the kernel's own device time read from the
trace's ``XLA Ops`` events named ``flash_fwd`` / ``flash_carry`` (the host
clock around the whole call, transposes included, is printed beside it). One
JSON line a measurement on standard output.

    python tools/flash_forward_timing.py                     # every shape
    python tools/flash_forward_timing.py --shapes cell,l4096
    python tools/flash_forward_timing.py --blocks 512,512,128 --blocks 256,1024,256
    python tools/flash_forward_timing.py --root .archive_check/parent   # another checkout

``--blocks bq,bk,sub`` overrides the forward's choice (a checkout whose forward
has no ``_forward_blocks`` runs its fixed default and takes no override).
Needs the TPU: a time from the CPU's interpreter says nothing.
"""

import argparse
import importlib
import json
import os
import sys
import tempfile
import time

# name -> (B, L, H, D, causal, kind)
SHAPES = {
    "cell": (8, 1024, 16, 64, True, "fwd"),        # gpt2m-* per chip and call
    "l4096": (4, 4096, 16, 64, True, "fwd"),
    "l8192": (4, 8192, 16, 64, True, "fwd"),
    "d128": (4, 2048, 16, 128, True, "fwd"),
    "noncausal": (8, 1024, 16, 64, False, "fwd"),
    # one ring step with traced offsets: the shard on the diagonal, and a
    # shard wholly before the queries
    "carry-diag": (4, 4096, 16, 64, True, "carry"),
    "carry-visible": (4, 4096, 16, 64, True, "carry"),
}
KERNELS = {"fwd": "flash_fwd", "carry": "flash_carry"}


def kernel_ms(trace_dir: str, kernel: str):
    """Durations (ms) of the device events of ``kernel`` in the newest trace."""
    from jax.profiler import ProfileData
    found = []
    for root, _, files in os.walk(trace_dir):
        found += [os.path.join(root, f) for f in files if f.endswith(".xplane.pb")]
    data = ProfileData.from_file(max(found, key=os.path.getmtime))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            out += [e.duration_ns * 1e-6 for e in line.events
                    if e.name.lstrip("%").startswith(kernel)]
    return out


def build(fa, name):
    import jax
    import jax.numpy as jnp

    b, length, h, d, causal, kind = SHAPES[name]
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(key, (b, length, h, d), jnp.bfloat16)
               for key in keys)
    if kind == "fwd":
        chooses = hasattr(fa, "_forward_blocks")
        default = None if chooses else fa.DEFAULT_Q_BLOCK
        fn = jax.jit(lambda q, k, v: fa._flash_forward(
            q, k, v, causal, default, default, False))
        args = (q, k, v)
    else:
        carry = (jnp.zeros((b, h, length, d), jnp.float32),
                 jnp.full((b, h, length), -1e30, jnp.float32),
                 jnp.zeros((b, h, length), jnp.float32))
        k_offset = length if name == "carry-diag" else 0
        fn = jax.jit(lambda q, k, v, carry, q_off, k_off:
                     fa.flash_attention_with_carry(
                         q, k, v, carry, causal=causal, q_offset=q_off,
                         k_offset=k_off))
        args = (q, k, v, carry, jnp.int32(length), jnp.int32(k_offset))
    return fn, args


def measure(fa, name, blocks, calls):
    import jax

    if blocks is not None:
        if not hasattr(fa, "_forward_blocks"):
            raise SystemExit("--blocks: this checkout's forward has a fixed default")
        fa._forward_blocks = lambda *a, **kw: blocks
    fn, args = build(fa, name)
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            t0 = time.perf_counter()
            outs = [fn(*args) for _ in range(calls)]
            jax.block_until_ready(outs)
            call = (time.perf_counter() - t0) / calls * 1e3
        kernel = sorted(kernel_ms(trace_dir, KERNELS[SHAPES[name][5]]))
    if not kernel:
        raise SystemExit(f"{name}: the trace holds no {KERNELS[SHAPES[name][5]]} event")
    record = {"shape": name, "blocks": blocks, "events": len(kernel),
              "kernel_ms_median": kernel[len(kernel) // 2],
              "kernel_ms_min": kernel[0], "call_ms_host": call}
    if SHAPES[name][5] == "fwd":
        from autodist_tpu import telemetry
        gauges = {k: v for k, v in telemetry.snapshot().items()
                  if k.startswith("flash.fwd.tiles_")}
        if gauges:      # a checkout older than the gauges has none
            record["tiles"] = gauges
    return record


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="checkout to import autodist_tpu from")
    parser.add_argument("--shapes", default=",".join(SHAPES))
    parser.add_argument("--blocks", action="append", default=[],
                        help="bq,bk,sub override; may repeat")
    parser.add_argument("--calls", type=int, default=20)
    args = parser.parse_args(argv)

    sys.path.insert(0, os.path.abspath(args.root))
    import jax
    if jax.default_backend() != "tpu":
        raise SystemExit(f"needs the TPU, the backend is {jax.default_backend()!r}")
    fa = importlib.import_module("autodist_tpu.ops.flash_attention")
    plans = [tuple(int(x) for x in b.split(",")) for b in args.blocks] or [None]
    for name in args.shapes.split(","):
        for blocks in plans:
            if blocks is not None and SHAPES[name][5] != "fwd":
                continue
            print(json.dumps({"root": args.root, **measure(fa, name, blocks,
                                                           args.calls)}),
                  flush=True)


if __name__ == "__main__":
    main()
