"""EVA attention (``ops/eva_attention.py``): the operator under both of its
forms, the quadratic ``impl="dot"`` and the two Pallas kernels (interpret mode
on the CPU), against the plain reference's window-at-a-time attention
(``benchmark/reference/evabyte.py``) — forward and all five gradients (q, k,
v, ``phi``, ``mu``) at one, two and three windows; window 0 is plain causal
attention; a query never sees a summary of its own window; the pair counts
the tile-fill gauge rests on against a brute-force mask."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.ops import eva_attention as ea
from benchmark.reference import evabyte as reference

WINDOW, CHUNK, HEADS, DEPTH = 32, 4, 2, 16


def _operands(length, dtype=jnp.float32, heads=HEADS, depth=DEPTH, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed + length), 6)
    q, k, v, w = (jax.random.normal(key, (2, length, heads, depth), dtype)
                  for key in keys[:4])
    # phi of the size of a key, so that the pooling weights are far from even
    return (q, k, v, 4.0 * jax.random.normal(keys[4], (heads, depth)),
            jax.random.normal(keys[5], (heads, depth)), w)


def _value_and_grads(attend, operands):
    *inputs, w = operands

    def loss(*inputs):
        out = attend(*inputs).astype(jnp.float32)
        return jnp.sum(out * w.astype(jnp.float32)), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(5)), has_aux=True))(*inputs)
    return out, grads


def _reference(window=WINDOW, chunk=CHUNK):
    def attend(q, k, v, phi, mu):
        with jax.default_matmul_precision("highest"):
            return reference.eva_attention(
                *(x.astype(jnp.float32) for x in (q, k, v)), phi, mu,
                window=window, chunk=chunk)
    return attend


def _close(got, want, tol):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.max(np.abs(got - want)) <= tol * max(np.max(np.abs(want)), 1.0)


@pytest.mark.parametrize("windows", [1, 2, 3])
@pytest.mark.parametrize("impl", ["dot", "kernel"])
def test_forward_and_five_gradients_match_the_reference_in_float32(impl, windows):
    operands = _operands(windows * WINDOW)
    out, grads = _value_and_grads(
        lambda *x: ea.eva_attention(*x, window=WINDOW, chunk=CHUNK, impl=impl),
        operands)
    want, want_grads = _value_and_grads(_reference(), operands)
    _close(out, want, 1e-5)
    for got, ref in zip(grads, want_grads):
        _close(got, ref, 1e-5)
    if windows == 1:        # no summary is seen: phi and mu move nothing
        assert not np.any(grads[3]) and not np.any(grads[4])
    else:
        assert np.any(grads[3]) and np.any(grads[4])
    assert telemetry.gauge("eva.windows").value == windows
    assert telemetry.gauge("eva.summaries").value == windows * WINDOW // CHUNK


@pytest.mark.parametrize("impl", ["dot", "kernel"])
def test_bfloat16_stays_inside_its_band(impl):
    """bfloat16 operands (8 bits of mantissa, 2^-8 a rounding) with float32
    softmax and accumulators: the result within 2e-2 of the float32
    reference's largest value, each gradient within 4e-2 of its largest
    (p and dS are rounded once more where they enter a product)."""
    operands = _operands(3 * WINDOW, jnp.bfloat16)
    out, grads = _value_and_grads(
        lambda *x: ea.eva_attention(*x, window=WINDOW, chunk=CHUNK, impl=impl),
        operands)
    want, want_grads = _value_and_grads(_reference(), operands)
    assert out.dtype == jnp.float32 and grads[0].dtype == jnp.bfloat16
    assert grads[3].dtype == jnp.float32
    _close(out, want, 2e-2)
    for got, ref in zip(grads, want_grads):
        _close(got, ref, 4e-2)


def test_the_kernels_at_the_cell_tiles_match_the_dot_form():
    """Heads of 128 (v, o and dO stay ``[B, L, H * D]`` rows), a window of
    two key tiles of 512 and a q block of 512: plain and masked tiles, two q
    blocks a window, blocks of summary tiles."""
    window, chunk = 1024, 16
    q, k, v, phi, mu, w = _operands(3 * window, heads=1, depth=128)
    operands = (q[:1], k[:1], v[:1], phi, mu, w[:1])
    out, grads = _value_and_grads(
        lambda *x: ea.eva_attention(*x, window=window, chunk=chunk), operands)
    want, want_grads = _value_and_grads(
        lambda *x: ea.eva_attention(*x, window=window, chunk=chunk, impl="dot"),
        operands)
    _close(out, want, 1e-5)
    for got, ref in zip(grads, want_grads):
        _close(got, ref, 2e-5)


@pytest.mark.parametrize("impl", ["dot", "kernel"])
def test_window_zero_is_plain_causal_attention(impl):
    q, k, v, phi, mu, _ = _operands(2 * WINDOW)
    out = ea.eva_attention(q, k, v, phi, mu, window=WINDOW, chunk=CHUNK,
                           impl=impl)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q[:, :WINDOW], k[:, :WINDOW]) \
        / np.sqrt(DEPTH)
    probs = jax.nn.softmax(jnp.where(
        jnp.tril(jnp.ones((WINDOW, WINDOW), bool)), scores, -1e30), axis=-1)
    _close(out[:, :WINDOW], jnp.einsum("bhqk,bkhd->bqhd", probs, v[:, :WINDOW]),
           1e-5)


@pytest.mark.parametrize("impl", ["dot", "kernel"])
def test_a_query_never_sees_a_summary_of_its_own_window(impl):
    """Move one key of window 1: the queries of window 1 before it (which
    would see it through its chunk's summary, were their own window's
    summaries in the softmax) and all of window 0 do not move; the queries
    from it on see it exactly, and window 2 through its summary."""
    q, k, v, phi, mu, _ = _operands(3 * WINDOW)
    at = WINDOW + 9
    moved = k.at[:, at].add(1.0)
    attend = lambda k: ea.eva_attention(  # noqa: E731
        q, k, v, phi, mu, window=WINDOW, chunk=CHUNK, impl=impl)
    change = np.abs(np.asarray(attend(moved) - attend(k))).max(axis=(0, 2, 3))
    assert not np.any(change[:at])
    assert np.all(change[at:2 * WINDOW] > 0) and np.all(change[2 * WINDOW:] > 0)


@pytest.mark.parametrize("length,window,chunk", [(96, 32, 4), (3072, 1024, 16),
                                                 (16384, 2048, 16)])
def test_pair_counts_match_a_brute_force_mask(length, window, chunk):
    visible, computed = ea.eva_pairs(length, window, chunk)
    i = np.arange(length)
    own = (i % window + 1).sum()
    earlier = (i // window * (window // chunk)).sum()
    assert visible == own + earlier
    bq = min(512, window)
    sub = bq if window % 512 == 0 or window < 512 else None
    tiles = sum(bq * (q_lo // window * (window // chunk)
                      + (q_lo % window // sub + 1) * sub)
                for q_lo in range(0, length, bq))
    assert computed == tiles and visible <= computed
    if length == 16384:     # the cell's call: flops_evabyte.py's own count
        assert (visible, computed) == (24_125_440, 28_311_552)


def test_shapes_that_are_no_whole_windows_or_chunks_are_refused():
    q, k, v, phi, mu, _ = _operands(48)
    with pytest.raises(ValueError, match="whole windows"):
        ea.eva_attention(q, k, v, phi, mu, window=WINDOW, chunk=CHUNK)
    with pytest.raises(ValueError, match="whole chunks"):
        ea.eva_attention(q, k, v, phi, mu, window=48, chunk=5)
    with pytest.raises(ValueError, match="Unknown impl"):
        ea.eva_attention(q[:, :32], k[:, :32], v[:, :32], phi, mu, window=32,
                         chunk=4, impl="flash")
