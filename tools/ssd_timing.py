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
K 4, forward and backward, against 4 and 6 bytes an element. ``norm`` lines
(PR 49): ``ops/gated_norm.py`` at ``[B, L, H P]`` in ``G`` runs, the two
kernels against XLA's lowering of ``gated_group_norm``, against 6 and 10
bytes an element (the backward's ``around_ms`` is ``dz`` padded to the wide
array's columns, which the model's XLA fuses into ``in_proj``'s transpose).

The kernels are handed what the model hands them (PR 49): ``[z | xBC | dt]``
as ``in_proj`` writes it, ``[B, L, 2 H P + 2 G N + H]``, of which the
convolution reads its window and the norm its ``z``, and the convolution's
``[x | B | C]`` rows whole to the scan; a checkout older than that
(``--root``) gets them cut out and reshaped inside the timed call, as its
model did, so ``device_ms`` - ``kernel_ms`` is what a form costs around its
kernel. ``operands_relaid`` is the operator's own gauge.

    python tools/ssd_timing.py
    python tools/ssd_timing.py --check    # the two paths' values, on the chip

``--check`` compares y and the gradients of the scan's two paths at the
shape, y, dx, dw and db of the convolution's and the norm's four, and exits 1
where they differ. Needs the TPU.
"""

import argparse
import functools
import inspect
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
CHECK_TOLERANCE = {"y": 1e-2, "dxbc": 1e-2, "ddt": 2e-2, "dA": 2e-2, "dD": 1e-2}


def takes_rows(ssd) -> bool:
    """Whether the checkout's scan takes ``[x | B | C]`` as one array."""
    return "groups" in inspect.signature(ssd.ssd_scan).parameters


def cut_apart(xbc, sizes):
    """``x [B, L, H, P]``, ``B``, ``C [B, L, G, N]`` out of the rows, as a
    model older than PR 49 made them."""
    import jax.numpy as jnp
    h, p, g, n = sizes
    x, b, c = jnp.split(xbc, [h * p, h * p + g * n], axis=-1)
    rows = xbc.shape[:2]
    return (x.reshape(*rows, h, p), b.reshape(*rows, g, n), c.reshape(*rows, g, n))


def build(ssd, direction: str, impl: str, operands, chunk: int, sizes):
    """One jitted call of the scan's forward or backward on ``operands =
    ((xbc, dt, A, D), dy [B, L, H P])``, what the custom VJP keeps."""
    import jax
    import jax.numpy as jnp
    inputs, dy = operands
    h, p, g, n = sizes
    rows = impl == "pallas" and takes_rows(ssd)

    def forward(xbc, dt, A, D):
        if rows:
            return ssd._forward_call(xbc, dt, A, None, None, D, chunk, False, (g, n))
        x, b, c = cut_apart(xbc, sizes)
        if impl == "xla":
            return ssd._xla_forward(x, dt, A, b, c, D, chunk)
        y, states = ssd._forward_call(x, dt, A, b, c, D, chunk, False)
        return y.reshape(*y.shape[:2], h * p), states

    if direction == "fwd":
        return jax.jit(forward), inputs
    states = jax.block_until_ready(jax.jit(forward)(*inputs))[1]

    def backward(xbc, dt, A, D, states, dy):
        if rows:
            return ssd._backward_call(xbc, dt, A, None, None, D, states, dy,
                                      chunk, False, (g, n))
        x, b, c = cut_apart(xbc, sizes)
        run = ssd._xla_backward if impl == "xla" else functools.partial(
            ssd._backward_call, interpret=False)
        dx, ddt, da, db, dc, dd = run(x, dt, A, b, c, D, states,
                                      dy.reshape(x.shape), chunk=chunk)
        flat = lambda t: t.reshape(*t.shape[:2], -1).astype(xbc.dtype)  # noqa: E731
        return (jnp.concatenate([flat(dx), flat(db), flat(dc)], axis=-1), ddt,
                da, dd)

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


def measure(ssd, direction, impl, operands, chunk, sizes, calls, least_ms):
    fn, args = build(ssd, direction, impl, operands, chunk, sizes)
    device, host, kernel = traced(fn, args, calls,
                                  f"ssd_{direction}" if impl == "pallas" else "")
    record = {"what": "scan", "direction": direction, "impl": impl,
              "device_ms": device, "call_ms_host": host, "least_ms": least_ms,
              "share_pct": 100 * least_ms / device}
    if impl == "pallas":
        if not kernel:
            raise SystemExit(f"the trace holds no ssd_{direction} event")
        record.update(kernel_record(kernel, least_ms, device),
                      operands_relaid=0 if takes_rows(ssd) else 4)
    return record


def kernel_record(kernel, least_ms, device):
    """The kernel's own events (median) beside every operation of the call."""
    alone = kernel[len(kernel) // 2]
    return {"kernel_ms": alone, "kernel_share_pct": 100 * least_ms / alone,
            "around_ms": device - alone}


def wide_operands(sizes, batch: int, length: int, k: int):
    """``[z | xBC | dt]`` as ``in_proj`` writes it, the convolution's taps and
    bias, and a cotangent of the convolution's width."""
    import jax
    import jax.numpy as jnp
    h, p, g, n = sizes
    d = h * p + 2 * g * n
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    # as wide as [z | xBC | dt], rounded up to whole lane tiles: a jit
    # PARAMETER whose last dimension is no multiple of 128 (10,304) arrives
    # with the positions minor and XLA copies all of it before the kernel
    # (0.5 ms a call, PR 49's first reading); in the model the array comes out
    # of ``in_proj``'s fusion in the kernels' layout and no such copy exists
    wide = -(-(h * p + d + h) // 128) * 128
    return (jax.random.normal(keys[0], (batch, length, wide), jnp.bfloat16),
            jax.random.normal(keys[1], (d, k), jnp.float32),
            jax.random.normal(keys[2], (d,), jnp.float32),
            jax.random.normal(keys[3], (batch, length, d), jnp.bfloat16))


def conv_both(sc, impl, at: int, d: int):
    """``(forward, backward)`` of ``conv_silu`` on one path, each one jitted
    call on the wide array; the backward on what the custom VJP keeps and
    ``dy``. The kernels of a checkout that takes a window read it where it
    lies; anything else gets it cut out inside the call."""
    import jax
    if impl == "pallas" and hasattr(sc, "_conv_silu_window_fwd"):
        interpret = sc._flash._use_interpret()
        fwd = jax.jit(lambda x, w, b: sc._silu_forward_call(x, w, b, interpret, at))
        bwd = jax.jit(lambda x, w, b, dy: sc._silu_backward_call(
            x, w, b, dy, interpret, at))
        return fwd, bwd
    cut = lambda x: x[..., at:at + d]  # noqa: E731
    fwd = jax.jit(lambda x, w, b: sc._conv_silu_fwd(cut(x), w, b, impl)[0])
    bwd = jax.jit(lambda x, w, b, dy: sc._conv_silu_bwd(impl, (cut(x), w, b), dy))
    return fwd, bwd


def measure_conv(sc, sizes, batch, length, k: int, calls: int):
    x, w, b, dy = wide_operands(sizes, batch, length, k)
    at, d = sizes[0] * sizes[1], w.shape[0]
    for impl in ("xla", "pallas"):
        fwd, bwd = conv_both(sc, impl, at, d)
        for direction, fn, args, moved in (("fwd", fwd, (x, w, b), 4),
                                           ("bwd", bwd, (x, w, b, dy), 6)):
            name = f"conv_silu_{direction}" if impl == "pallas" else ""
            device, host, kernel = traced(fn, args, calls, name)
            least = dy.size * moved / HBM_BYTES_PER_S * 1e3
            record = {"what": "conv", "direction": direction, "impl": impl,
                      "device_ms": device, "call_ms_host": host,
                      "least_ms": least, "share_pct": 100 * least / device}
            if kernel:
                record.update(kernel_record(kernel, least, device),
                              operands_relaid=int(not hasattr(
                                  sc, "_conv_silu_window_fwd")))
            yield record


def norm_both(gn, impl, sizes):
    """``(forward, backward)`` of the gated norm on ``y [B, L, H P]`` and the
    wide array whose first columns are ``z``, each one jitted call."""
    import jax
    import jax.numpy as jnp
    h, p, g, _ = sizes

    def forward(y, wide, scale):
        return gn.gated_norm(y, wide, scale, g, 1e-5, jnp.bfloat16, impl)

    def backward(y, wide, scale, dy):
        return jax.vjp(forward, y, wide, scale)[1](dy)

    return jax.jit(forward), jax.jit(backward)


def measure_norm(gn, sizes, batch, length, calls: int):
    import jax
    import jax.numpy as jnp
    wide = wide_operands(sizes, batch, length, 1)[0]
    h, p = sizes[:2]
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    y = jax.random.normal(keys[0], (batch, length, h * p), jnp.bfloat16)
    dy = jax.random.normal(keys[1], y.shape, jnp.bfloat16)
    scale = jnp.ones((h * p,), jnp.float32)
    for impl in ("xla", "pallas"):
        fwd, bwd = norm_both(gn, impl, sizes)
        # forward y, z in and the result out; backward y, z, dy in and dy, dz
        # out (the forward under jax.vjp is dead code there and does not run)
        for direction, fn, args, moved in (("fwd", fwd, (y, wide, scale), 6),
                                           ("bwd", bwd, (y, wide, scale, dy), 10)):
            name = "gated_norm_bwd" if direction == "bwd" else "gated_norm_fwd"
            device, host, kernel = traced(fn, args, calls,
                                          name if impl == "pallas" else "")
            least = y.size * moved / HBM_BYTES_PER_S * 1e3
            record = {"what": "norm", "direction": direction, "impl": impl,
                      "device_ms": device, "call_ms_host": host,
                      "least_ms": least, "share_pct": 100 * least / device}
            if kernel:
                record.update(kernel_record(kernel, least, device))
            yield record


def check_conv(sc, sizes, batch, length, k: int):
    """The convolution's two paths value for value on the chip: y, dx, dw, db."""
    x, w, b, dy = wide_operands(sizes, batch, length, k)
    at, d = sizes[0] * sizes[1], w.shape[0]
    parts = {}
    for impl in ("pallas", "xla"):
        fwd, bwd = conv_both(sc, impl, at, d)
        parts[impl] = (fwd(x, w, b), *bwd(x, w, b, dy))
    record = {"check": "conv", **{
        name: _distance(a, b_) for name, a, b_ in zip(
            ("conv_y", "conv_dx", "conv_dw", "conv_db"), *parts.values())}}
    # bfloat16 results of float32 arithmetic in another order; dw and db are
    # float32 sums over every position
    record["agree"] = all(record[name] <= 5e-3 for name in
                          ("conv_y", "conv_dx", "conv_dw", "conv_db"))
    return record


def check_norm(gn, sizes, batch, length):
    """The gated norm's two paths on the chip: the result, dy, dz, dscale."""
    import jax
    import jax.numpy as jnp
    wide = wide_operands(sizes, batch, length, 1)[0]
    h, p = sizes[:2]
    y = jax.random.normal(jax.random.PRNGKey(2), (batch, length, h * p),
                          jnp.bfloat16)
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(3), (h * p,))
    parts = {}
    for impl in ("pallas", "xla"):
        fwd, bwd = norm_both(gn, impl, sizes)
        parts[impl] = (fwd(y, wide, scale), *bwd(y, wide, scale, y))
    names = ("norm_y", "norm_dy", "norm_dz", "norm_dscale")
    record = {"check": "norm", **{name: _distance(a, b) for name, a, b in zip(
        names, *parts.values())}}
    # bfloat16 roundings of float32 arithmetic in another order
    record["agree"] = all(record[name] <= 5e-3 for name in names)
    return record


def _distance(a, b):
    import jax.numpy as jnp
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def check(ssd, operands, chunk: int, sizes):
    """The scan's two paths on the chip, the kernels on what the model hands
    (rows, where the checkout takes them): y, d [x | B | C], ddt, dA, dD."""
    import jax
    (xbc, dt, A, D), dy = operands
    h, p, g, n = sizes

    def both(impl):
        def scan(xbc, dt, A, D):
            if takes_rows(ssd):
                return ssd.ssd_scan(xbc, dt, A, None, None, D, chunk=chunk,
                                    impl=impl, groups=(g, n))
            x, b, c = cut_apart(xbc, sizes)
            y = ssd.ssd_scan(x, dt, A, b, c, D, chunk=chunk, impl=impl)
            return y.reshape(*y.shape[:2], h * p)

        def run(*inputs):
            y, vjp = jax.vjp(scan, *inputs)
            return (y, *vjp(dy))
        return jax.jit(run)(xbc, dt, A, D)

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
    sizes = (h, p, g, n)
    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    inputs = (
        jax.random.normal(keys[0], (b, length, h * p + 2 * g * n), jnp.bfloat16),
        jax.nn.softplus(jax.random.normal(keys[1], (b, length, h)) - 3.0),
        -jnp.arange(1, h + 1, dtype=jnp.float32),       # A = -exp(A_log) at init
        jnp.ones((h,), jnp.float32))
    operands = (inputs, jax.random.normal(keys[6], (b, length, h * p), jnp.bfloat16))
    try:
        from autodist_tpu.ops import gated_norm as gn
    except ImportError:         # a checkout older than PR 49
        gn = None

    def emit(record):
        print(json.dumps({"shape": args.shape, "chunk": args.chunk, **record}),
              flush=True)

    if args.check:
        records = [check(ssd, operands, args.chunk, sizes),
                   check_conv(sc, sizes, b, length, args.k)]
        if gn is not None:
            records.append(check_norm(gn, sizes, b, length))
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
            emit(measure(ssd, direction, impl, operands, args.chunk, sizes,
                         args.calls, cost.least_seconds(chip) * 1e3))
    for record in measure_conv(sc, sizes, b, length, args.k, args.calls):
        emit(record)
    if gn is not None:
        for record in measure_norm(gn, sizes, b, length, args.calls):
            emit(record)


if __name__ == "__main__":
    main()
