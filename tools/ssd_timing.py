#!/usr/bin/env python3
"""Time the chunked state-space scan alone, on the chip: the two Pallas
kernels of ``ops/ssd_scan.py`` against XLA's lowering of the same chunked
equations, and the same for the convolution before the scan
(``ops/short_conv.py`` ``conv_silu``).

The instrument behind ``ssm_impl``.
At ``--shape B,L,H,P,G,N`` (default the Nemotron cell's 1,8192,64,64,8,128,
chunk ``--chunk`` 128, bfloat16 ``x``, ``B``, ``C``, float32 ``dt``) it runs,
under the profiler, the forward and the backward of each path as one jitted
call (the backward on the inputs, the chunk states and ``dy``, which is all the
custom VJP keeps) and prints one JSON line a measurement: ``device_ms`` = every
device operation of a call summed (the plain path is many fusions, the Pallas
path its kernel and the small layout and cumsum operations around it),
``kernel_ms`` = the Pallas kernel's own events (median), and ``least_ms`` =
the larger of the scan's products at the chip's bf16 peak and its bytes at its
memory bandwidth
(``benchmark/flops_nemotron_h.py`` ``ssd_cost``), with the share of it.
``conv`` lines: ``ops/short_conv.py`` ``conv_silu`` at ``[B, L, H P + 2 G N]``,
K 4, forward and backward, against 4 and 6 bytes an element.

    python tools/ssd_timing.py
    python tools/ssd_timing.py --check    # the two paths' values, on the chip

``--check`` compares y and the six gradients of the scan's two paths at the
shape, and y, dx, dw and db of the convolution's, and exits 1 where they differ. Needs the TPU.
"""

import argparse
import json
import os
import sys
import tempfile
import time

from flash_forward_timing import kernel_ms   # device events of a trace by name

HBM_BYTES_PER_S = 819e9         # TPU v5e, benchmark/peaks.py
# relative L2 distance up to which the kernels agree with the plain path:
# both round their products' operands to bfloat16 and accumulate in float32,
# in another order; dA and dD are float32 sums over every position
CHECK_TOLERANCE = {"y": 1e-2, "dx": 1e-2, "ddt": 2e-2, "dA": 2e-2, "dB": 1e-2,
                   "dC": 1e-2, "dD": 1e-2}


def build(ssd, direction: str, impl: str, operands, chunk: int):
    import jax
    inputs, dy = operands
    forward = {"xla": lambda *a: ssd._xla_forward(*a, chunk),
               "pallas": lambda *a: ssd._forward_call(*a, chunk, False)}[impl]
    if direction == "fwd":
        return jax.jit(forward), inputs
    states = jax.block_until_ready(jax.jit(forward)(*inputs))[1]
    backward = {
        "xla": lambda *a: ssd._xla_backward(*a, chunk),
        "pallas": lambda *a: ssd._backward_call(*a, chunk, False)}[impl]
    return jax.jit(backward), (*inputs, states, dy)


def traced(fn, args, calls: int, name: str):
    import jax
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            t0 = time.perf_counter()
            jax.block_until_ready([fn(*args) for _ in range(calls)])
            host = (time.perf_counter() - t0) / calls * 1e3
        every = kernel_ms(trace_dir, "")       # every device operation
        kernel = sorted(kernel_ms(trace_dir, name)) if name else []
    return sum(every) / calls, host, kernel


def measure(ssd, direction, impl, operands, chunk, calls, least_ms):
    fn, args = build(ssd, direction, impl, operands, chunk)
    device, host, kernel = traced(fn, args, calls,
                                  f"ssd_{direction}" if impl == "pallas" else "")
    record = {"what": "scan", "direction": direction, "impl": impl,
              "device_ms": device, "call_ms_host": host, "least_ms": least_ms,
              "share_pct": 100 * least_ms / device}
    if impl == "pallas":
        if not kernel:
            raise SystemExit(f"the trace holds no ssd_{direction} event")
        record["kernel_ms"] = kernel[len(kernel) // 2]
        record["kernel_share_pct"] = 100 * least_ms / record["kernel_ms"]
    return record


def conv_operands(shape, k: int):
    import jax
    import jax.numpy as jnp
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    return (jax.random.normal(keys[0], shape, jnp.bfloat16),
            jax.random.normal(keys[1], (shape[2], k), jnp.float32),
            jax.random.normal(keys[2], (shape[2],), jnp.float32),
            jax.random.normal(keys[3], shape, jnp.bfloat16))


def conv_both(sc, impl):
    """``(forward, backward)`` of ``conv_silu`` on one path, each one jitted
    call; the backward on what the custom VJP keeps and ``dy``."""
    import jax
    fwd = jax.jit(lambda x, w, b: sc._conv_silu_fwd(x, w, b, impl)[0])
    bwd = jax.jit(lambda x, w, b, dy: sc._conv_silu_bwd(impl, (x, w, b), dy))
    return fwd, bwd


def measure_conv(sc, shape, k: int, calls: int):
    x, w, b, dy = conv_operands(shape, k)
    for impl in ("xla", "pallas"):
        fwd, bwd = conv_both(sc, impl)
        for direction, fn, args, moved in (("fwd", fwd, (x, w, b), 4),
                                           ("bwd", bwd, (x, w, b, dy), 6)):
            name = f"conv_silu_{direction}" if impl == "pallas" else ""
            device, host, kernel = traced(fn, args, calls, name)
            least = x.size * moved / HBM_BYTES_PER_S * 1e3
            record = {"what": "conv", "direction": direction, "impl": impl,
                      "device_ms": device, "call_ms_host": host,
                      "least_ms": least, "share_pct": 100 * least / device}
            if kernel:
                record["kernel_ms"] = kernel[len(kernel) // 2]
                record["kernel_share_pct"] = 100 * least / record["kernel_ms"]
            yield record


def check_conv(sc, shape, k: int):
    """The convolution's two paths value for value on the chip: y, dx, dw, db."""
    x, w, b, dy = conv_operands(shape, k)
    parts = {}
    for impl in ("pallas", "xla"):
        fwd, bwd = conv_both(sc, impl)
        parts[impl] = (fwd(x, w, b), *bwd(x, w, b, dy))
    record = {"check": "conv", **{
        name: _distance(a, b_) for name, a, b_ in zip(
            ("conv_y", "conv_dx", "conv_dw", "conv_db"), *parts.values())}}
    # bfloat16 results of float32 arithmetic in another order; dw and db are
    # float32 sums over every position
    record["agree"] = all(record[name] <= 5e-3 for name in
                          ("conv_y", "conv_dx", "conv_dw", "conv_db"))
    return record


def _distance(a, b):
    import jax.numpy as jnp
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def check(ssd, operands, chunk: int):
    import jax
    inputs, dy = operands

    def both(impl):
        def run(*inputs):
            y, vjp = jax.vjp(
                lambda *a: ssd.ssd_scan(*a, chunk=chunk, impl=impl), *inputs)
            return (y, *vjp(dy))
        return jax.jit(run)(*inputs)

    got, want = both("pallas"), both("xla")
    record = {"check": True, **{part: _distance(a, b) for part, a, b
                                in zip(CHECK_TOLERANCE, got, want)}}
    record["agree"] = all(record[part] <= limit     # a NaN agrees with nothing
                          for part, limit in CHECK_TOLERANCE.items())
    return record


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="checkout to import autodist_tpu from")
    parser.add_argument("--shape", default="1,8192,64,64,8,128",
                        help="B,L,H,P,G,N")
    parser.add_argument("--chunk", type=int, default=128)
    parser.add_argument("--k", type=int, default=4)
    parser.add_argument("--check", action="store_true")
    parser.add_argument("--calls", type=int, default=10)
    args = parser.parse_args(argv)

    sys.path.insert(0, os.path.abspath(args.root))
    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu":
        raise SystemExit(f"needs the TPU, the backend is {jax.default_backend()!r}")
    from autodist_tpu.ops import short_conv as sc, ssd_scan as ssd
    from benchmark import flops_nemotron_h, peaks

    b, length, h, p, g, n = (int(x) for x in args.shape.split(","))
    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    inputs = (
        jax.random.normal(keys[0], (b, length, h, p), jnp.bfloat16),
        jax.nn.softplus(jax.random.normal(keys[1], (b, length, h)) - 3.0),
        -jnp.arange(1, h + 1, dtype=jnp.float32),       # A = -exp(A_log) at init
        jax.random.normal(keys[3], (b, length, g, n), jnp.bfloat16),
        jax.random.normal(keys[4], (b, length, g, n), jnp.bfloat16),
        jnp.ones((h,), jnp.float32))
    operands = (inputs, jax.random.normal(keys[6], (b, length, h, p), jnp.bfloat16))

    def emit(record):
        print(json.dumps({"shape": args.shape, "chunk": args.chunk, **record}),
              flush=True)

    conv_shape = (b, length, h * p + 2 * g * n)
    if args.check:
        records = [check(ssd, operands, args.chunk),
                   check_conv(sc, conv_shape, args.k)]
        for record in records:
            emit(record)
        if not all(record["agree"] for record in records):
            raise SystemExit(1)
        return
    costs = flops_nemotron_h.ssd_cost(tokens=b * length, s=dict(
        mamba_heads=h, mamba_head_dim=p, n_groups=g, d_state=n, chunk=args.chunk))
    chip = peaks.peaks_for(jax.devices()[0].device_kind)
    for direction, cost in zip(("fwd", "bwd"), costs):
        for impl in ("xla", "pallas"):
            emit(measure(ssd, direction, impl, operands, args.chunk, args.calls,
                         cost.least_seconds(chip) * 1e3))
    for record in measure_conv(sc, conv_shape, args.k, args.calls):
        emit(record)


if __name__ == "__main__":
    main()
