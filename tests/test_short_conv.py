"""The gated short convolution (``ops/short_conv.py``): both paths against
three shifted products written out here, value and all four gradients (dB,
dC, du, dw), with a block edge on the sequence edge, a sequence the block does
not divide, three and four taps; nothing crosses from one sequence of a batch
to the next; the custom VJP keeps ``bcu`` and ``w`` and nothing else; the
gauges say what a call moves; the cells' calls of this file's two operators
trace the jaxprs they traced before ``conv_silu`` took a window of a wider
array. The kernels run in interpret mode on the CPU."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.ops import short_conv
from autodist_tpu.ops.short_conv import gated_short_conv


def shifted_products(bcu, w):
    """``y_t = C_t * sum_j w_j * v_{t-(K-1)+j}``, ``v = B * u``, one shifted
    product a tap: the array moved down by ``K - 1 - j`` rows behind zeros."""
    k = w.shape[1]
    b, c, u = jnp.split(bcu.astype(jnp.float32), 3, axis=-1)
    v = b * u
    total = jnp.zeros_like(v)
    for j in range(k):
        s = k - 1 - j
        moved = v if s == 0 else jnp.concatenate(
            [jnp.zeros_like(v[:, :s]), v[:, :v.shape[1] - s]], axis=1)
        total = total + w[:, j] * moved
    return c * total


def _operands(batch, length, d, k, dtype=jnp.float32, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (batch, length, 3 * d), dtype),
            jax.random.normal(keys[1], (d, k), jnp.float32),
            jax.random.normal(keys[2], (batch, length, d), dtype))


@pytest.fixture
def blocks(monkeypatch):
    def set_blocks(rows, channels=128):
        monkeypatch.setattr(short_conv, "FWD_BLOCK_ROWS", rows)
        monkeypatch.setattr(short_conv, "BWD_BLOCK_ROWS", rows)
        monkeypatch.setattr(short_conv, "_CHANNELS", channels)
    return set_blocks


CASES = {
    # two sequences, two row blocks each: a block edge lies on the sequence edge
    "edge-on-the-sequence-edge": (2, 32, 128, 3, 16),
    # the block does not divide the sequence: a ragged last block
    "ragged-last-block": (1, 40, 128, 3, 32),
    "ragged-two-sequences": (2, 24, 128, 3, 16),
    "four-taps": (2, 48, 256, 4, 16),
    "four-taps-ragged": (1, 40, 128, 4, 32),
    # one block holds the whole sequence, and more (a block of 48 rows for 40)
    "one-block": (2, 40, 256, 3, 64),
    "shorter-than-a-tile": (2, 8, 128, 3, 16),
}


@pytest.mark.parametrize("impl", short_conv.IMPLS)
@pytest.mark.parametrize("case", CASES)
def test_value_and_all_four_gradients_match_three_shifted_products(
        impl, case, blocks):
    batch, length, d, k, rows = CASES[case]
    blocks(rows)
    bcu, w, dy = _operands(batch, length, d, k)
    y, vjp = jax.vjp(lambda bcu, w: gated_short_conv(bcu, w, impl), bcu, w)
    want, want_vjp = jax.vjp(shifted_products, bcu, w)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    (dbcu, dw), (want_dbcu, want_dw) = vjp(dy), want_vjp(dy)
    for got, ref in zip(jnp.split(dbcu, 3, axis=-1),          # dB, dC, du
                        jnp.split(want_dbcu, 3, axis=-1)):
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dw, want_dw, rtol=1e-4, atol=1e-4)
    assert y.shape == (batch, length, d) and dw.shape == (d, k)


@pytest.mark.parametrize("impl", short_conv.IMPLS)
def test_nothing_crosses_from_one_sequence_to_the_next(impl, blocks):
    """Position 0 of every sequence sees zeros before it: another first
    sequence changes nothing of the second one's output or gradient, and the
    first row is the last tap alone."""
    blocks(16)
    bcu, w, dy = _operands(2, 32, 128, 3)
    other = bcu.at[0].set(_operands(1, 32, 128, 3, seed=7)[0][0])

    def run(bcu):
        y, vjp = jax.vjp(lambda bcu: gated_short_conv(bcu, w, impl), bcu)
        return y, vjp(dy)[0]

    (y, dbcu), (y_other, dbcu_other) = run(bcu), run(other)
    np.testing.assert_array_equal(y[1], y_other[1])
    np.testing.assert_array_equal(dbcu[1], dbcu_other[1])
    assert float(jnp.abs(y[0] - y_other[0]).max()) > 0
    b, c, u = jnp.split(bcu, 3, axis=-1)
    np.testing.assert_allclose(y[:, 0], c[:, 0] * w[:, 2] * b[:, 0] * u[:, 0],
                               rtol=1e-5, atol=1e-6)
    # the last row's v reaches no later row: its gradient is the last tap's alone
    np.testing.assert_allclose(
        jnp.split(dbcu, 3, axis=-1)[0][:, -1],
        w[:, 2] * dy[:, -1] * c[:, -1] * u[:, -1], rtol=1e-5, atol=1e-6)


def test_bfloat16_operands_are_computed_in_float32_and_rounded_once():
    bcu, w, dy = _operands(2, 64, 256, 3, jnp.bfloat16)
    got = jax.vjp(lambda bcu, w: gated_short_conv(bcu, w, "pallas"), bcu, w)
    want = jax.vjp(lambda bcu, w: gated_short_conv(bcu, w, "xla"), bcu, w)
    exact = jax.vjp(shifted_products, bcu, w)
    assert got[0].dtype == jnp.bfloat16
    for out in (got, want):         # one rounding of a float32 result: a
        # bfloat16 unit at most, where the taps were summed in another order
        np.testing.assert_allclose(out[0].astype(jnp.float32), exact[0],
                                   rtol=2 ** -7, atol=1e-6)
    (dbcu, dw), (want_dbcu, want_dw) = got[1](dy), want[1](dy)
    assert dbcu.dtype == jnp.bfloat16 and dw.dtype == jnp.float32
    np.testing.assert_allclose(dbcu.astype(jnp.float32),
                               want_dbcu.astype(jnp.float32), rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(dw, want_dw, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("impl", short_conv.IMPLS)
def test_the_backward_keeps_bcu_and_w_and_nothing_else(impl):
    from jax._src.ad_checkpoint import saved_residuals
    bcu, w, _ = _operands(2, 32, 128, 3, jnp.bfloat16)
    kept = saved_residuals(lambda bcu, w: gated_short_conv(bcu, w, impl), bcu, w)
    assert sorted(tuple(aval.shape) for aval, _ in kept) == \
        sorted([bcu.shape, w.shape])


def test_gauges_say_what_a_call_moves_by_its_block_specs(blocks):
    blocks(16)
    calls = telemetry.snapshot().get("short_conv.calls", 0)
    bcu, w, dy = _operands(2, 32, 128, 3, jnp.bfloat16)
    jax.vjp(lambda bcu, w: gated_short_conv(bcu, w, "pallas"), bcu, w)[1](dy)
    gauges = telemetry.snapshot()
    assert gauges["short_conv.calls"] == calls + 1
    assert gauges["short_conv.fwd.block_rows"] == 16
    assert gauges["short_conv.bwd.block_rows"] == 16
    tensor = 2 * 32 * 128 * 2                   # a [T, d] bfloat16 tensor
    taps = 3 * 128 * 4
    # forward: B, C, u in, y out, the taps once
    assert gauges["short_conv.fwd.bytes"] == 4 * tensor + taps
    # backward: B, C, u, dy in, dbcu out, 16 rows of C and dy after each of
    # the 4 blocks, the taps in and their gradient out
    halo = 4 * 2 * 16 * 128 * 2
    assert gauges["short_conv.bwd.bytes"] == 7 * tensor + halo + 2 * taps
    # the plain path counts no call
    gated_short_conv(bcu, w, "xla")
    assert telemetry.snapshot()["short_conv.calls"] == calls + 1


def test_arguments_the_operator_cannot_take_are_refused():
    bcu, w, _ = _operands(1, 16, 128, 3)
    with pytest.raises(ValueError, match="Unknown conv impl"):
        gated_short_conv(bcu, w, "mosaic")
    with pytest.raises(ValueError, match=r"\[B, L, 3d\]"):
        gated_short_conv(bcu[..., :256], w, "pallas")
    with pytest.raises(ValueError, match="multiple of 128"):
        gated_short_conv(bcu[..., :192], w[:64], "pallas")
    # the plain path takes any width
    assert gated_short_conv(bcu[..., :192], w[:64], "xla").shape == (1, 16, 64)


F32, BF16 = jnp.float32, jnp.bfloat16
# a cell's call of this file's operators -> the SHA-256 of its jaxpr at the
# commit before ``conv_silu`` took a window (75acf8b; regenerate there with
# the test's own lines if the kernels change on purpose)
CELL_CALLS = {
    "jamba2-sharded4-16k": (
        lambda x, w, b: short_conv.conv_silu(x, w, b, "pallas"),
        (((1, 16384, 5120), BF16), ((5120, 4), F32), ((5120,), F32)),
        "f1eefb0f64e4d29467b801224b5d9eeb8ec24fb5d729aa9307b08e78f3c5416b"),
    "jamba2-sharded4-16k-precise-layer": (
        lambda x, w, b: short_conv.conv_silu(x, w, b, "pallas"),
        (((1, 16384, 5120), F32), ((5120, 4), F32), ((5120,), F32)),
        "a7b96ee517856e11ea9c3117e9e5bc2c59381dca708ca7fca2f3f43850009693"),
    "nemotron-cut-out": (
        lambda x, w, b: short_conv.conv_silu(x, w, b, "pallas"),
        (((1, 8192, 6144), BF16), ((6144, 4), F32), ((6144,), F32)),
        "d5e387193601ca1f738d38870b01c05aa9cca39a19a810abfb26ebf8042a837a"),
    "lfm2-pretrain-8k": (
        lambda bcu, w: gated_short_conv(bcu, w, "pallas"),
        (((2, 8192, 6144), BF16), ((2048, 3), F32)),
        "8750114dd667fbaa9e9cccf7347ca24d55a68905cb1f29cd88c7c6d3b9b2e8ba"),
}


@pytest.mark.parametrize("name", list(CELL_CALLS))
def test_a_cells_call_traces_the_jaxpr_it_traced_before(name):
    """The jaxpr of a call and its gradients AT THE CELL'S SIZE, the kernels'
    bodies and block specs included (traced from shapes: nothing is lowered
    or run): an operand as wide as the taps is what it always was, so Jamba's
    and LFM2's steps compile what they compiled."""
    fn, shapes, parent = CELL_CALLS[name]
    args = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in shapes]
    text = str(jax.make_jaxpr(jax.value_and_grad(
        lambda *a: fn(*a).astype(jnp.float32).sum(),
        argnums=tuple(range(len(args)))))(*args))
    assert hashlib.sha256(text.encode()).hexdigest() == parent
