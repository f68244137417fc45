"""Flash attention with a learned sink a query head (``ops/flash_attention.py``
``flash_attention(..., sink=s)``): one more logit in every query's softmax
that carries no value. Forward, dq / dk / dv and d sink against the dot path
with the sink as a concatenated column of the logits, under windows on both
sides of a lane tile (128) and of a key tile (512), grouped KV heads and keys
wider than values; the band's tile classes against a brute-force mask at those
windows; and a call without a sink lowers to what it lowered to before there
were sinks. Interpret mode on the CPU, the kernels' own default tiles."""

import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.models.mimo_v2 import sink_dot_attention

fa = importlib.import_module("autodist_tpu.ops.flash_attention")

PARENT_SHA256 = "d9dfc50e6a6eb7f999f6a462db6985cd829d87f8973b8253f9fa67055fbf52d8"
WINDOWS = (1, 127, 128, 129, 512, 513, None)
LENGTH = 600      # two q blocks of 512 (the second ragged), K/V resident in two key tiles


def sink_attention(q, k, v, sink, window):
    """The dot path: ``models/mimo_v2.py`` ``sink_dot_attention``, the sink
    one more column of the float32 logits whose probability meets no value."""
    return sink_dot_attention(q, k, v, window, sink, jnp.float32)


def _operands(length, heads, kv_heads, d_qk, d_v, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(keys[0], (1, length, heads, d_qk)),
            jax.random.normal(keys[1], (1, length, kv_heads, d_qk)),
            jax.random.normal(keys[2], (1, length, kv_heads, d_v)),
            # sinks on both sides of the scores' range (unit-variance logits)
            2.0 * jax.random.normal(keys[3], (heads,)),
            jax.random.normal(keys[4], (1, length, heads, d_v)))


def _cases():
    """Every window with the (group, widths) pairs in rotation: each window,
    each group and both widths at least twice, and the cell's own window of
    128 with its 8 query heads a KV head at 192 / 128."""
    pairs = [(group, widths) for group in (1, 8, 16)
             for widths in ((192, 128), (128, 128))]
    return [(w, *pairs[i % len(pairs)]) for i, w in enumerate(WINDOWS)]


@pytest.mark.parametrize("window,group,widths", _cases(), ids=lambda x: str(x))
def test_sink_forward_and_gradients_match_the_concatenated_column(window, group,
                                                                  widths):
    d_qk, d_v = widths
    kv_heads = 2 if group == 1 else 1
    q, k, v, sink, w = _operands(LENGTH, kv_heads * group, kv_heads, d_qk, d_v,
                                 seed=group + (window or 0))

    def run(attend):
        def loss(q, k, v, sink):
            out = attend(q, k, v, sink)
            return jnp.sum(out * w), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3), has_aux=True))(q, k, v, sink)
        return out, grads

    out, grads = run(lambda q, k, v, s: fa.flash_attention(
        q, k, v, causal=True, window=window, sink=s))
    assert telemetry.gauge("flash.window").value == (window or 0)
    assert telemetry.gauge("flash.kv_group").value == group
    assert telemetry.gauge("flash.d_qk").value == d_qk
    assert telemetry.gauge("flash.d_v").value == d_v
    want, want_grads = run(lambda q, k, v, s: sink_attention(q, k, v, s, window))
    # float32 throughout: the kernels' online softmax and XLA's differ by
    # summation order only
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    for name, got, ref in zip(("dq", "dk", "dv", "dsink"), grads, want_grads):
        scale = float(jnp.max(jnp.abs(ref))) or 1.0
        np.testing.assert_allclose(got / scale, ref / scale, atol=3e-5,
                                   err_msg=name)
    # the sink takes probability: without it the result differs
    plain = fa.flash_attention(q, k, v, causal=True, window=window)
    assert float(jnp.max(jnp.abs(plain - want))) > 1e-3


def test_a_very_low_sink_is_no_sink_and_a_very_high_one_takes_everything():
    q, k, v, _, _ = _operands(300, 4, 2, 64, 64)
    plain = fa.flash_attention(q, k, v, causal=True, window=128)
    low = fa.flash_attention(q, k, v, causal=True, window=128,
                             sink=jnp.full((4,), -1e4))
    np.testing.assert_allclose(low, plain, atol=1e-6)
    high = fa.flash_attention(q, k, v, causal=True, window=128,
                              sink=jnp.full((4,), 1e4))
    np.testing.assert_allclose(high, jnp.zeros_like(high), atol=1e-6)


def test_the_split_backward_and_bfloat16_operands_carry_the_sink(monkeypatch):
    """The two-kernel backward (past ``_RESIDENT_DQ_BYTES``) reads the same
    log-sum-exp, so it holds the sink too; bfloat16 operands keep the sink
    float32 (it reaches the kernel through SMEM, never as an operand's
    dtype)."""
    monkeypatch.setattr(fa, "_RESIDENT_DQ_BYTES", 0)
    q, k, v, sink, w = _operands(200, 4, 2, 64, 64, seed=3)

    def grads(attend, cast):
        loss = lambda q, k, v, s: jnp.sum(  # noqa: E731
            attend(cast(q), cast(k), cast(v), s).astype(jnp.float32) * w)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))(q, k, v, sink)

    same = lambda x: x  # noqa: E731
    got = grads(lambda q, k, v, s: fa.flash_attention(
        q, k, v, causal=True, window=70, sink=s, q_block=64, k_block=64), same)
    assert telemetry.gauge("flash.bwd.passes").value == 2
    want = grads(lambda q, k, v, s: sink_attention(q, k, v, s, 70), same)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=3e-5 * float(jnp.max(jnp.abs(b))))
    half = lambda x: x.astype(jnp.bfloat16)  # noqa: E731
    got16 = grads(lambda q, k, v, s: fa.flash_attention(
        q, k, v, causal=True, window=70, sink=s), half)
    assert got16[3].dtype == jnp.float32
    # bfloat16 products: 2^-8 a rounding, the sums float32
    np.testing.assert_allclose(got16[3], want[3],
                               atol=0.03 * float(jnp.max(jnp.abs(want[3]))))


def test_a_sink_must_be_one_logit_a_query_head():
    q, k, v, _, _ = _operands(64, 4, 2, 32, 32)
    with pytest.raises(ValueError, match="one logit a query head"):
        fa.flash_attention(q, k, v, sink=jnp.zeros((2,)))


def _classes(length, window, bq, bk, sub):
    """For every (q block, K/V block) pair of a causal call: the counts
    ``_band_tile_counts`` gives, and per key tile whether the mask keeps
    every pair, some, or none of it (brute force)."""
    i, j = np.arange(length)[:, None], np.arange(length)[None, :]
    visible = j <= i
    if window is not None:
        visible &= i - j < window
    n_q, n_k = -(-length // bq), -(-length // bk)
    padded = np.zeros((n_q * bq, n_k * bk), bool)
    padded[:length, :length] = visible
    # a padded QUERY row sees what a real one there would (the forward masks
    # keys alone; the backward's dO is zero there)
    for qi in range(n_q):
        for ki in range(n_k):
            counts = fa._band_tile_counts(qi * bq, ki * bk,
                                          fa._valid_keys(length, ki * bk, bk),
                                          bq, bk, sub, True, window)
            rows = slice(qi * bq, min((qi + 1) * bq, length))
            kept = [padded[rows, ki * bk + t * sub: ki * bk + (t + 1) * sub]
                    for t in range(bk // sub)]
            yield tuple(int(c) for c in counts), [
                "all" if t.all() else "some" if t.any() else "none" for t in kept]


@pytest.mark.parametrize("window", WINDOWS, ids=lambda w: f"window-{w}")
@pytest.mark.parametrize("length,bq,bk,sub", [
    (2048, 512, 2048, 512),      # the cell's form: K/V resident, four key tiles
    (1100, 512, 1536, 512),      # ragged queries and keys
    (1024, 256, 256, 128),       # streamed blocks, tiles of one lane tile
], ids=["resident", "ragged", "streamed"])
def test_band_tile_classes_against_a_brute_force_mask(window, length, bq, bk, sub):
    """Skipped tiles hold nothing the mask keeps, plain tiles nothing it
    hides, and every tile that holds a kept pair is walked."""
    for (n_lo, n_ps, n_pe, n_need), kept in _classes(length, window, bq, bk, sub):
        assert 0 <= n_lo <= n_ps <= n_pe <= n_need <= bk // sub
        for t, what in enumerate(kept):
            if t < n_lo or t >= n_need:
                assert what == "none", (window, t, what)
            elif n_ps <= t < n_pe:
                assert what == "all", (window, t, what)


def test_a_window_of_128_under_512_tiles_walks_two_masked_tiles_a_q_block():
    """What ``mimo-sharded4-8k``'s sliding layers run: no plain tile, so no
    straight-line block forms, and an eighth of what is computed is visible."""
    plain, masked, skipped = fa._count_tiles(8192, 8192, 512, 8192, 512, True, 128)
    assert (plain, masked, skipped) == (0, 31, 16 * 16 - 31)
    visible, computed = fa.band_pairs(8192, 8192, True, 128, d=192)
    assert visible == 128 * 129 // 2 + (8192 - 128) * 128
    assert computed == 31 * 512 * 512
    assert 12.5 < 100.0 * visible / computed < 13.0
    full_visible, full_computed = fa.band_pairs(8192, 8192, True, None, d=192)
    assert full_visible == 8192 * 8193 // 2
    assert full_computed == (16 * 17 // 2) * 512 * 512


def _lowered(sink):
    q = jax.ShapeDtypeStruct((1, 256, 4, 64), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, 256, 2, 64), jnp.float32)
    args = (q, kv, kv) + ((jax.ShapeDtypeStruct((4,), jnp.float32),)
                          if sink else ())
    return jax.jit(jax.grad(
        lambda q, k, v, s=None: fa.flash_attention(
            q, k, v, causal=True, window=100, sink=s).sum(),
        argnums=(0, 1, 2))).lower(*args).as_text()


def test_a_call_without_a_sink_lowers_to_what_it_lowered_to_before():
    """The text a windowed, grouped call lowers to (interpret mode: plain
    StableHLO, no line numbers), by its SHA-256 at the commit before the sink
    (e1da577; regenerate there with this function if the kernels change on
    purpose). With a sink the program differs and names the sink's kernels."""
    plain = _lowered(sink=False)
    assert hashlib.sha256(plain.encode()).hexdigest() == PARENT_SHA256
    with_sink = _lowered(sink=True)
    assert with_sink != plain

