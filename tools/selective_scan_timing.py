#!/usr/bin/env python3
"""Time the Mamba-1 selective scan alone, on the chip: the two Pallas kernels
of ``ops/selective_scan.py`` and, with ``--plain``, XLA's lowering of the same
recurrence (a ``lax.scan`` over every token: seconds a call at the cell's
length, so it is off by default).

The instrument behind ``ssm_impl`` for the Jamba family and behind the scan's
chunk. At ``--shape B,L,E,N`` (default the Jamba cell's 1,16384,5120,16, a
chip's share; bfloat16 ``x``, or ``--x-dtype float32`` as the cell's precise
first layer hands it; float32 ``dt``, ``B``, ``C``) and each ``--chunk`` (may
repeat) it runs, under the profiler, the forward and the
backward as one jitted call each (the backward on the inputs, the chunk states
and ``dy``, which is all the custom VJP keeps) and prints one JSON line a
measurement: ``device_ms`` = every device operation of a call summed (the
kernel and whatever XLA puts around it), ``kernel_ms`` = the Pallas kernel's
own events (median), ``layout_ms`` = ``device_ms`` - ``kernel_ms`` (since PR
44 the kernels take ``x``, ``dt``, ``dy`` and hand back ``y``, ``dx``,
``ddt`` as the ``[B, L, E]`` rows the model holds, so this is the casts of
``B`` / ``C`` and the small results' reshapes: under 0.1 ms; a checkout older
than that reads 2.1 ms forward and 3.9 backward here, the wide operands laid
out anew as ``[B, L, E / 128, 128]``), ``least_ms`` = the kernel's bytes at
the chip's memory bandwidth (``benchmark/flops_jamba.py``
``selective_scan_cost``; the kernel has no product for the MXU and the VPU
bounds it, so the share reads low) and ``ns_per_state_element`` =
``kernel_ms`` over ``B L E N``. ``--root <checkout>`` times another checkout
in the same call.

    python tools/selective_scan_timing.py
    python tools/selective_scan_timing.py --check    # against the plain path, on the chip

``--check`` compares y and the six gradients of the two paths at ``--shape``
(default for the check: 1,2048,5120,16) and exits 1 where they differ;
``digest`` is of the kernels' seven results' bytes, so two checkouts
(``--root``) whose kernels do the same arithmetic in the same order print the
same one. Needs the TPU.
"""

import argparse
import hashlib
import json
import os
import sys

import numpy as np
from ssd_timing import _distance, traced   # a call under the profiler; relative L2

# relative L2 distance up to which the kernels agree with the plain path: both
# compute in float32, in another order (exp2 of a scaled product for exp)
CHECK_TOLERANCE = {"y": 1e-2, "dx": 1e-2, "ddt": 1e-3, "dA": 1e-3, "dB": 1e-3,
                   "dC": 1e-3, "dD": 1e-3}


def build(ss, direction: str, impl: str, operands, chunk: int):
    import jax
    inputs, dy = operands
    forward = {"xla": lambda *a: ss._xla_forward(*a, chunk),
               "pallas": lambda *a: ss._forward_call(*a, chunk, False)}[impl]
    if direction == "fwd":
        return jax.jit(forward), inputs
    states = jax.block_until_ready(jax.jit(forward)(*inputs))[1]
    backward = {
        "xla": lambda *a: ss._xla_backward(*a, chunk),
        "pallas": lambda *a: ss._backward_call(*a, chunk, False)}[impl]
    return jax.jit(backward), (*inputs, states, dy)


def measure(ss, direction, impl, operands, chunk, calls, least_ms, elements):
    fn, args = build(ss, direction, impl, operands, chunk)
    name = f"selective_scan_{direction}" if impl == "pallas" else ""
    device, host, kernel = traced(fn, args, calls, name)
    record = {"direction": direction, "impl": impl, "chunk": chunk,
              "device_ms": device, "call_ms_host": host, "least_ms": least_ms,
              "share_pct": 100 * least_ms / device}
    if impl == "pallas":
        if not kernel:
            raise SystemExit(f"the trace holds no {name} event")
        record["kernel_ms"] = kernel[len(kernel) // 2]
        record["layout_ms"] = device - record["kernel_ms"]
        record["kernel_share_pct"] = 100 * least_ms / record["kernel_ms"]
        record["ns_per_state_element"] = record["kernel_ms"] * 1e6 / elements
    return record


def check(ss, operands, chunk: int):
    import jax
    inputs, dy = operands

    def both(impl):
        def run(*inputs):
            y, vjp = jax.vjp(lambda *a: ss.selective_scan(
                *a, chunk=chunk, impl=impl), *inputs)
            return (y, *vjp(dy))
        return jax.jit(run)(*inputs)

    got, want = both("pallas"), both("xla")
    digest = hashlib.sha256()
    for part in got:
        digest.update(np.asarray(part).tobytes())
    record = {"check": True, "chunk": chunk, "digest": digest.hexdigest()[:16],
              **{part: _distance(a, b) for part, a, b
                 in zip(CHECK_TOLERANCE, got, want)}}
    record["agree"] = all(record[part] <= limit     # a NaN agrees with nothing
                          for part, limit in CHECK_TOLERANCE.items())
    return record


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="checkout to import autodist_tpu from")
    parser.add_argument("--shape", default=None, help="B,L,E,N")
    parser.add_argument("--chunk", type=int, action="append")
    parser.add_argument("--x-dtype", default="bfloat16",
                        choices=("bfloat16", "float32"), help="of x and dy")
    parser.add_argument("--check", action="store_true")
    parser.add_argument("--plain", action="store_true",
                        help="time XLA's lowering of the recurrence too")
    parser.add_argument("--calls", type=int, default=5)
    args = parser.parse_args(argv)

    sys.path.insert(0, os.path.abspath(args.root))
    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu":
        raise SystemExit(f"needs the TPU, the backend is {jax.default_backend()!r}")
    from autodist_tpu.ops import selective_scan as ss
    from benchmark import flops_jamba, peaks

    shape = args.shape or ("1,2048,5120,16" if args.check else "1,16384,5120,16")
    b, length, e, n = (int(x) for x in shape.split(","))
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    x_dtype = jnp.dtype(args.x_dtype)
    inputs = (
        jax.random.normal(keys[0], (b, length, e), x_dtype),
        jax.nn.softplus(jax.random.normal(keys[1], (b, length, e)) - 3.0),
        -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (e, n)),
        jax.random.normal(keys[2], (b, length, n), jnp.float32),
        jax.random.normal(keys[3], (b, length, n), jnp.float32),
        jnp.ones((e,), jnp.float32))
    operands = (inputs, jax.random.normal(keys[5], (b, length, e), x_dtype))
    chip = peaks.peaks_for(jax.devices()[0].device_kind)

    def emit(record):
        print(json.dumps({"shape": shape, "x_dtype": args.x_dtype, **record}),
              flush=True)

    agree = True
    for chunk in args.chunk or [ss.DEFAULT_CHUNK]:
        if args.check:
            record = check(ss, operands, chunk)
            emit(record)
            agree = agree and record["agree"]
            continue
        costs = flops_jamba.selective_scan_cost(
            tokens=b * length, s=dict(d_inner=e, d_state=n, chunk=chunk),
            act_bytes=x_dtype.itemsize)
        for direction, cost in zip(("fwd", "bwd"), costs):
            for impl in ("xla", "pallas") if args.plain else ("pallas",):
                emit(measure(ss, direction, impl, operands, chunk, args.calls,
                             cost.least_seconds(chip) * 1e3, b * length * e * n))
    if not agree:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
