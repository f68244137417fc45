"""The chunked state-space scan (``ops/ssd_scan.py``): both paths against the
literal step-by-step recurrence written out here and against the exact
quadratic form of the benchmark's reference, value and the gradient of every
input; lengths that are and are not whole chunks; nothing crosses from one
sequence of a batch to the next; chunk 128 and a smaller one; the custom VJP
keeps the inputs and one state a chunk and head; the gauges say what a call
moves; ``conv_silu`` (``ops/short_conv.py``) against shifted products; both
operators handed their operands as column windows of a wider array (the rows
a neighbour wrote) give the cut-apart call's numbers bit for bit, and a call
that hands what it always did traces the jaxpr it always traced. The kernels
run in interpret mode on the CPU."""

import functools
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.ops import short_conv, ssd_scan as ssd
from autodist_tpu.ops.ssd_scan import ssd_scan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def recurrence(x, dt, A, B, C, D):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D
    x_t``, a position at a time, one ``[P, N]`` state a head."""
    b, _, h, p = x.shape
    g, n = B.shape[2:]
    Bh, Ch = jnp.repeat(B, h // g, axis=2), jnp.repeat(C, h // g, axis=2)

    def step(state, at):
        xt, dtt, bt, ct = at
        state = (jnp.exp(dtt * A)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[..., None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct) + D[:, None] * xt

    _, ys = jax.lax.scan(step, jnp.zeros((b, h, p, n)),
                         tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, Bh, Ch)))
    return jnp.moveaxis(ys, 0, 1)


def quadratic(x, dt, A, B, C, D):
    from benchmark.reference import nemotron_h as reference
    h, g = x.shape[2], B.shape[2]
    return reference.quadratic_ssm(x, dt, dt * A, jnp.repeat(B, h // g, axis=2),
                                   jnp.repeat(C, h // g, axis=2), D)


def _operands(b, length, h, p, g, n, seed=0, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    inputs = (jax.random.normal(keys[0], (b, length, h, p), dtype),
              jax.nn.softplus(jax.random.normal(keys[1], (b, length, h)) - 1.0),
              -jnp.exp(0.5 * jax.random.normal(keys[2], (h,))),
              (0.3 * jax.random.normal(keys[3], (b, length, g, n))).astype(dtype),
              (0.3 * jax.random.normal(keys[4], (b, length, g, n))).astype(dtype),
              jax.random.normal(keys[5], (h,)))
    return inputs, jax.random.normal(keys[6], (b, length, h, p))


def _value_and_grads(fn, inputs, weight):
    """``((loss, its six gradients), fn's result)``: one compiled program and
    one forward (eagerly the interpreted kernels and the recurrence run
    operation by operation, the forward twice)."""
    def loss(*a):
        y = fn(*a)
        return jnp.sum(y.astype(jnp.float32) * weight), y
    (value, y), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(6)), has_aux=True))(*inputs)
    return (value, grads), y


# (b, L, H, P, G, N, chunk): the kernels want N, a group's heads x P and the
# chunk in multiples of 128
CASES = {
    "xla-whole-chunks": ("xla", (2, 32, 4, 8, 2, 16, 16)),
    "xla-ragged": ("xla", (2, 40, 4, 8, 2, 16, 16)),
    "xla-shorter-than-a-chunk": ("xla", (1, 9, 2, 8, 1, 16, 16)),
    "xla-chunk-128": ("xla", (1, 200, 2, 8, 2, 16, 128)),
    "xla-one-head-a-group": ("xla", (2, 48, 3, 8, 3, 16, 16)),
    "pallas-two-chunks-two-sequences": ("pallas", (2, 256, 4, 64, 2, 128, 128)),
    "pallas-ragged": ("pallas", (1, 200, 2, 64, 1, 128, 128)),
    "pallas-eight-heads-a-group": ("pallas", (1, 128, 8, 16, 1, 128, 128)),
}


# the same calls with [x | B | C] handed as one array of rows, as the
# convolution writes them: case -> the cut-apart case it must equal
ROWS = {"xla-rows-ragged": "xla-ragged",
        "pallas-rows-two-chunks-two-sequences": "pallas-two-chunks-two-sequences",
        "pallas-rows-ragged": "pallas-ragged"}


def _as_rows(chunk, impl):
    """``ssd_scan`` on ``[x | B | C]`` put together first, result and
    gradients back in the cut-apart call's shapes."""
    def fn(x, dt, A, B, C, D):
        flat = lambda t: t.reshape(*t.shape[:2], -1)  # noqa: E731
        y = ssd_scan(jnp.concatenate([flat(x), flat(B), flat(C)], axis=-1), dt,
                     A, None, None, D, chunk=chunk, impl=impl,
                     groups=B.shape[2:])
        return y.reshape(x.shape)
    return fn


@functools.lru_cache(maxsize=None)
def _scanned(case):
    """A case's operands and what ``ssd_scan`` makes of them, once for the
    forms it is held against."""
    impl, (*shape, chunk) = CASES[ROWS.get(case, case)]
    inputs, weight = _operands(*shape)
    fn = (_as_rows(chunk, impl) if case in ROWS
          else lambda *a: ssd_scan(*a, chunk=chunk, impl=impl))
    return inputs, weight, _value_and_grads(fn, inputs, weight)


@pytest.mark.parametrize("against", ["recurrence", "quadratic"])
@pytest.mark.parametrize("case", list(CASES) + list(ROWS))
def test_values_and_every_gradient_match(case, against):
    inputs, weight, ((_, got), y) = _scanned(case)
    if case in ROWS:
        # the rows are the cut-apart call's: the kernels' bit for bit (the
        # same blocks of the same numbers), the plain path's to a float32
        # rounding (XLA fuses the cuts into its products as it likes)
        _, _, ((_, cut), cut_y) = _scanned(ROWS[case])
        for a, b in zip((y, *got), (cut_y, *cut)):
            if CASES[ROWS[case]][0] == "pallas":
                np.testing.assert_array_equal(a, b)
            else:
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    plain = {"recurrence": recurrence, "quadratic": quadratic}[against]
    (_, want), want_y = _value_and_grads(plain, inputs, weight)
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-4)
    for name, g, r in zip(("dx", "ddt", "dA", "dB", "dC", "dD"), got, want):
        assert g.shape == r.shape and g.dtype == r.dtype, name
        scale = float(jnp.abs(r).max())
        np.testing.assert_allclose(g, r, rtol=1e-3, atol=2e-4 * scale,
                                   err_msg=name)


@pytest.mark.parametrize("impl,shape", [("xla", (2, 40, 4, 8, 2, 16, 16)),
                                        ("pallas", (2, 200, 2, 64, 1, 128, 128))])
def test_no_state_crosses_from_one_sequence_to_the_next(impl, shape):
    """The second sequence's result is what it gives alone, whatever the
    first holds; and the first sequence's inputs take no gradient from the
    second's outputs."""
    *dims, chunk = shape
    inputs, weight = _operands(*dims)
    fn = jax.jit(lambda *a: ssd_scan(*a, chunk=chunk, impl=impl))
    alone = fn(*(t[1:] if t.ndim > 1 else t for t in inputs))
    np.testing.assert_allclose(fn(*inputs)[1:], alone, rtol=1e-5, atol=1e-5)
    loud = tuple(t.at[0].multiply(50.0) if t.ndim == 4 else t for t in inputs)
    np.testing.assert_allclose(fn(*loud)[1:], alone, rtol=1e-5, atol=1e-5)
    second_only = weight.at[0].set(0.0)
    (_, grads), _ = _value_and_grads(fn, inputs, second_only)
    for g in (grads[0], grads[1], grads[3], grads[4]):
        assert float(jnp.abs(g[0]).max()) == 0.0 < float(jnp.abs(g[1]).max())


def test_bfloat16_operands_accumulate_in_float32_and_agree_across_paths():
    """The models' dtypes: bfloat16 ``x``, ``B``, ``C``, float32 ``dt``; the
    result and dx, dB, dC come back bfloat16, ddt, dA, dD float32; the two
    paths round the same operands and agree to a rounding or two."""
    inputs, weight = _operands(1, 256, 4, 64, 2, 128, dtype=jnp.bfloat16)
    out = {}
    for impl in ssd.IMPLS:
        fn = lambda *a, impl=impl: ssd_scan(*a, impl=impl)  # noqa: E731
        (_, grads), y = _value_and_grads(fn, inputs, weight)
        assert y.dtype == jnp.bfloat16
        assert [g.dtype for g in grads] == [jnp.bfloat16, jnp.float32,
                                            jnp.float32, jnp.bfloat16,
                                            jnp.bfloat16, jnp.float32]
        out[impl] = (y, *grads)
    f32 = tuple(t.astype(jnp.float32) for t in inputs)
    (_, exact), exact_y = _value_and_grads(recurrence, f32, weight)
    for a, b, r in zip(out["pallas"], out["xla"], (exact_y, *exact)):
        norm = float(jnp.linalg.norm(r))
        assert float(jnp.linalg.norm(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))) <= 1e-2 * norm
        assert float(jnp.linalg.norm(a.astype(jnp.float32) - r)) <= 2e-2 * norm


@pytest.mark.parametrize("impl", ssd.IMPLS)
def test_the_backward_keeps_the_inputs_and_one_state_a_chunk_and_head(impl):
    """Residuals of the custom VJP: the six inputs and ``[b, chunks, H, P,
    N]`` float32, nothing with a ``[Q, Q]`` plane in it."""
    inputs, _ = _operands(1, 256, 4, 64, 2, 128)
    _, residuals = ssd._scan_fwd(*inputs, 128, impl)
    *kept, states = residuals
    for a, b in zip(kept, inputs):
        assert a is b
    assert states.dtype == jnp.float32 and states.size == 2 * 4 * 64 * 128


@pytest.mark.parametrize("rows,relaid", [(False, 4), (True, 0)],
                         ids=["cut-apart", "rows"])
def test_gauges_and_the_counter_are_set_when_the_operator_is_traced(rows, relaid):
    """``ssd.operands_relaid``: x, B, C and the result cross the kernels'
    boundary in another shape on a cut-apart call, none of them as rows."""
    calls = telemetry.counter("ssd.calls").value
    inputs, _ = _operands(2, 200, 4, 64, 2, 128, dtype=jnp.bfloat16)
    fn = (_as_rows(128, "pallas") if rows
          else lambda *a: ssd_scan(*a, impl="pallas"))
    jax.eval_shape(fn, *inputs)
    assert telemetry.counter("ssd.calls").value == calls + 1
    assert telemetry.gauge("ssd.operands_relaid").value == relaid
    assert [telemetry.gauge(f"ssd.{k}").value for k in
            ("chunk", "chunks", "heads", "groups", "state")] == [128, 4, 4, 2, 128]
    wide, narrow = 2 * 200 * 4 * 64 * 2, 2 * 200 * 2 * 128 * 2
    states = 4 * 4 * 64 * 128 * 4
    assert telemetry.gauge("ssd.fwd.bytes").value == 2 * wide + 2 * narrow + states
    assert telemetry.gauge("ssd.bwd.bytes").value == 3 * wide + 4 * narrow + states


def test_mismatched_arguments_and_unknown_impls_are_refused():
    (x, dt, A, B, C, D), _ = _operands(1, 16, 4, 8, 2, 16)
    with pytest.raises(ValueError, match="Unknown ssd impl"):
        ssd_scan(x, dt, A, B, C, D, impl="mosaic")
    with pytest.raises(ValueError, match="G dividing H"):
        ssd_scan(x, dt, A, B[:, :, :1].repeat(3, axis=2), C, D)
    with pytest.raises(ValueError, match="want"):
        ssd_scan(x, dt[:, :8], A, B, C, D)
    with pytest.raises(ValueError, match="multiples of 128"):
        ssd_scan(x, dt, A, B, C, D, chunk=16, impl="pallas")


@pytest.mark.parametrize("impl,heads,width,groups,refusal", [
    # 2 heads of 64 end at column 128, but 3 heads of 64 at 192: B would
    # begin inside a block of the state's 128 columns
    ("pallas", 3, 192 + 2 * 128, (1, 128), "off a block of 128"),
    ("pallas", 2, 128 + 2 * 128, None, r"groups None says \(G, N\)"),
    ("xla", 2, 128 + 2 * 128 + 1, (1, 128), "are not 2 heads of P"),
    ("xla", 3, 192 + 4 * 128, (2, 128), "G dividing H"),
], ids=["cut-off-a-lane-tile", "groups-left-out", "columns-do-not-add-up",
        "groups-do-not-divide-the-heads"])
def test_rows_the_operator_cannot_take_are_refused_by_name(impl, heads, width,
                                                           groups, refusal):
    xbc, dt = jnp.zeros((1, 128, width)), jnp.ones((1, 128, heads))
    with pytest.raises(ValueError, match=refusal):
        ssd_scan(xbc, dt, -jnp.ones(heads), None, None, jnp.ones(heads),
                 impl=impl, groups=groups)
    if impl == "pallas" and groups:     # the plain path cuts anywhere
        assert ssd_scan(xbc, dt, -jnp.ones(heads), None, None, jnp.ones(heads),
                        groups=groups).shape == (1, 128, 192)


def _sha(fn, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in shapes]
    return hashlib.sha256(str(jax.make_jaxpr(jax.value_and_grad(
        lambda *a: fn(*a).astype(jnp.float32).sum(),
        argnums=tuple(range(len(args)))))(*args)).encode()).hexdigest()


# (B, L, H, P, G, N), x's dtype -> the SHA-256 of the call's jaxpr at the
# commit before the rows (75acf8b; regenerate there with ``_sha`` if the
# kernels change on purpose)
PARENT_SCANS = {
    "nemotron-cell": ((1, 8192, 64, 64, 8, 128), jnp.bfloat16,
                      "cd3488e4ba9fc216f8e4a6792f75eba196e79b53d194cbbf77ff1e6842a0f34e"),
    "ragged-float32": ((1, 1000, 4, 64, 2, 128), jnp.float32,
                       "0782fa523e8acd760a8908e2c6df2a1570042e49668d93b38d636d149a3099b8"),
}


@pytest.mark.parametrize("name", list(PARENT_SCANS))
def test_a_four_dimensional_call_traces_the_jaxpr_it_traced_before(name):
    """The jaxpr of a cut-apart call and its six gradients, the kernels'
    bodies and block specs included (traced from shapes: nothing is lowered
    or run): what the caller hands is the signal, so a call that hands what
    it always did compiles what it always did."""
    (b, length, h, p, g, n), dtype, parent = PARENT_SCANS[name]
    f32 = jnp.float32
    assert _sha(lambda *a: ssd_scan(*a, impl="pallas"),
                ((b, length, h, p), dtype), ((b, length, h), f32), ((h,), f32),
                ((b, length, g, n), dtype), ((b, length, g, n), dtype),
                ((h,), f32)) == parent


# ------------------------------------------- the convolution before the scan

def _shifted_silu(x, w, b):
    k = w.shape[1]
    total = jnp.zeros(x.shape, jnp.float32)
    for j in range(k):
        s = k - 1 - j
        moved = x if s == 0 else jnp.concatenate(
            [jnp.zeros_like(x[:, :s]), x[:, :x.shape[1] - s]], axis=1)
        total = total + w[:, j] * moved
    return jax.nn.silu(total + b)


@pytest.mark.parametrize("impl,batch,length,d,k,window", [
    ("xla", 2, 24, 48, 4, None), ("xla", 1, 3, 16, 4, None),
    ("xla", 2, 17, 32, 3, None),
    # the kernels: a length of whole row blocks, a ragged one of three row
    # blocks whose last holds 8 rows, one shorter than a walk step, and two
    # channel blocks of a grid (2,560 = 2 x 1,280)
    ("pallas", 2, 512, 256, 4, None), ("pallas", 2, 520, 128, 4, None),
    ("pallas", 1, 3, 128, 4, None), ("pallas", 2, 40, 2560, 3, None),
    # the operand as columns ``at : at + d`` of a wider array, (at, width):
    # anywhere on the plain path; for the kernels behind one lane tile, at
    # the front of an array that ends off one (as [z | xBC | dt] does), and
    # two channel blocks behind two more
    ("xla", 2, 24, 48, 4, (5, 64)), ("pallas", 2, 520, 256, 4, (128, 640)),
    ("pallas", 2, 40, 256, 4, (0, 320)), ("pallas", 2, 40, 2560, 3, (2560, 5184)),
], ids=["four-taps", "shorter-than-the-taps", "three-taps", "kernels-whole-blocks",
        "kernels-ragged", "kernels-shorter-than-a-step", "kernels-two-channel-blocks",
        "window", "kernels-window-ragged", "kernels-window-in-front",
        "kernels-window-two-channel-blocks"])
def test_conv_silu_and_its_three_gradients_match_shifted_products(
        impl, batch, length, d, k, window):
    at, width = window or (0, d)
    conv = jax.jit(functools.partial(short_conv.conv_silu, impl=impl, at=at))
    cut = lambda x: x[..., at:at + d]  # noqa: E731
    plain = lambda x, w, b: _shifted_silu(cut(x), w, b)  # noqa: E731
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(keys[0], (batch, length, width))
    w = jax.random.normal(keys[1], (d, k))
    b = jax.random.normal(keys[2], (d,))
    weight = jax.random.normal(keys[3], (batch, length, d))
    loss = lambda fn: lambda *a: jnp.sum(fn(*a) * weight)  # noqa: E731
    np.testing.assert_allclose(conv(x, w, b), plain(x, w, b),
                               rtol=1e-5, atol=1e-5)
    got = jax.jit(jax.grad(loss(conv), argnums=(0, 1, 2)))(x, w, b)
    want = jax.jit(jax.grad(loss(plain), argnums=(0, 1, 2)))(x, w, b)
    for g, r in zip(got, want):
        assert g.shape == r.shape
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4 * float(jnp.abs(r).max()))
    # each sequence on its own, and only x, w and b kept for the backward
    np.testing.assert_allclose(conv(x, w, b)[-1:], conv(x[-1:], w, b), rtol=1e-6)
    if window and impl == "pallas":     # the wide array itself, nothing cut
        kept = (x, w, b)
        _, residuals = short_conv._conv_silu_window_fwd(*kept, at)
    else:
        kept = (cut(x), w, b)
        _, residuals = short_conv._conv_silu_fwd(*kept, impl)
    assert [r is a for r, a in zip(residuals, kept)] == [True] * 3
    out = conv(x.astype(jnp.bfloat16), w, b)
    assert out.dtype == jnp.bfloat16 and out.shape == (batch, length, d)
    if window:      # the window's numbers are the cut-out operand's, bit for bit
        apart = jax.jit(lambda x, w, b: short_conv.conv_silu(cut(x), w, b, impl))
        np.testing.assert_array_equal(conv(x, w, b), apart(x, w, b))
        for g, r in zip(got, jax.jit(jax.grad(loss(apart), argnums=(0, 1, 2)))(
                x, w, b)):
            np.testing.assert_array_equal(g, r)


def test_conv_silu_refuses_what_its_kernels_cannot_take():
    x, w, b = jnp.zeros((1, 8, 48)), jnp.zeros((48, 4)), jnp.zeros((48,))
    with pytest.raises(ValueError, match="multiple of 128"):
        short_conv.conv_silu(x, w, b, impl="pallas")
    with pytest.raises(ValueError, match="Unknown conv impl"):
        short_conv.conv_silu(x, w, b, impl="mosaic")
    with pytest.raises(ValueError, match=r"want \[B, L, d\]"):
        short_conv.conv_silu(x, jnp.zeros((64, 4)), b, impl="pallas")
    # a wider x is a window: it must lie inside, and for the kernels begin
    # on a lane tile's edge
    wide, w, b = jnp.zeros((1, 8, 512)), jnp.zeros((128, 4)), jnp.zeros((128,))
    with pytest.raises(ValueError, match="columns 448:576"):
        short_conv.conv_silu(wide, w, b, at=448)
    with pytest.raises(ValueError, match="first column 64 is not on a lane tile"):
        short_conv.conv_silu(wide, w, b, impl="pallas", at=64)
    assert short_conv.conv_silu(wide, w, b, at=64).shape == (1, 8, 128)


@pytest.mark.parametrize("window,relaid", [(False, 1), (True, 0)],
                         ids=["cut-out", "window"])
def test_the_convolutions_gauge_counts_an_operand_handed_cut_out(window, relaid):
    x = jax.ShapeDtypeStruct((1, 64, 512 if window else 256), jnp.bfloat16)
    w, b = jnp.zeros((256, 4)), jnp.zeros((256,))
    jax.eval_shape(lambda x: short_conv.conv_silu(
        x, w, b, "pallas", at=256 if window else 0), x)
    assert telemetry.gauge("short_conv.operands_relaid").value == relaid
