"""Flash attention with a learned sink a query head (``ops/flash_attention.py``
``flash_attention(..., sink=s)``): one more logit in every query's softmax
that carries no value. Forward, dq / dk / dv and d sink against the dot path
with the sink as a concatenated column of the logits, under windows on both
sides of a lane tile (128) and of a key tile (512), grouped KV heads and keys
wider than values; the band's tile classes, and the tiles of the walk fitted
to a window narrower than a key tile, against a brute-force mask at those
windows; and a call without a window, or with one at least a key tile wide,
lowers to what it lowered to before the walk was fitted. Interpret mode on
the CPU, the kernels' own default tiles."""

import hashlib
import importlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.models.mimo_v2 import sink_dot_attention

fa = importlib.import_module("autodist_tpu.ops.flash_attention")

# window -> SHA-256 of the text ``_lowered`` gives at the commit before the
# fitted walk (653cb21)
PARENT_SHA256 = {
    None: "bfcd778e5e3c83da7d73beb8e870380be613b5782a96e55e231abf3d8f369277",
    2048: "c7953ca192e16a3c480e695e8cf6934aa0273058613579536084e70b58b67f72",
    512: "ae483c26981e04d413b45546837277192be45064f761fae0b0b9f296ec69b3c1",
}
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


def _fitted_tiles(length, window, bq, bk, sub, span):
    """The tiles of the fitted walk of a causal call, forward and backward,
    as ``(queries, keys)`` slices: for every (q block, K/V block) pair the
    forward's grid runs, a 128-query chunk against the ``span`` keys
    ``_band_key_starts`` names; for every K/V block of the backward's one
    pass (``bk`` keys against all the queries, padded to whole q blocks), a
    128-key chunk against the ``span`` queries ``_band_query_starts``
    names."""
    n_q, n_k = -(-length // bq), -(-length // bk)
    forward = []
    for qi, ki in itertools.product(range(n_q), range(n_k)):
        n_lo, _, _, n_need = fa._band_tile_counts(
            qi * bq, ki * bk, fa._valid_keys(length, ki * bk, bk), bq, bk, sub,
            True, window)
        if n_need > n_lo:       # the grid step runs
            forward += [
                (slice(qi * bq + c, qi * bq + c + 128),
                 slice(ki * bk + start, ki * bk + start + span))
                for c, start in zip(range(0, bq, 128), fa._band_key_starts(
                    qi * bq, ki * bk, bq, bk, span))]
    rows = n_q * bq
    back_span = fa._band_span(window, bk, rows, bq)
    backward = [
        (slice(start, start + back_span), slice(ki * bk + c, ki * bk + c + 128))
        for ki in range(n_k)
        for c, start in zip(range(0, bk, 128), fa._band_query_starts(
            0, ki * bk, rows, bk, back_span))]
    return forward, backward


@pytest.mark.parametrize("window", WINDOWS, ids=lambda w: f"window-{w}")
@pytest.mark.parametrize("length,bq,bk,sub", [
    (2048, 512, 2048, 512),      # the cell's form: K/V resident, four key tiles
    (1100, 512, 1536, 512),      # ragged queries and keys
    (1024, 256, 256, 128),       # streamed blocks, tiles of one lane tile
    (1024, 512, 512, 512),       # the backward's blocks at the cell's form
], ids=["resident", "ragged", "streamed", "backward"])
def test_every_visible_pair_lies_in_exactly_one_tile_of_the_fitted_walk(
        window, length, bq, bk, sub):
    """A window at least a tile wide keeps the tiles' own walk; a narrower
    one is walked in ``[span, 128]`` tiles that hold every pair the mask
    keeps once, in the forward and in the backward."""
    span = fa._band_span(window, bq, bk, sub)
    assert (span > 0) == (window is not None and window < sub)
    if not span:
        return
    # whole lane tiles, the fewest that hold a chunk's 128 + window - 1 keys
    assert span % 128 == 0
    assert span == bk or window + 127 <= span < window + 255
    i, j = np.arange(length)[:, None], np.arange(length)[None, :]
    visible = (j <= i) & (i - j < window)
    n_q, n_k = -(-length // bq), -(-length // bk)
    for tiles in _fitted_tiles(length, window, bq, bk, sub, span):
        met = np.zeros((n_q * bq, n_k * bk), int)
        for queries, keys in tiles:
            assert 0 <= queries.start and queries.stop <= n_q * bq
            assert 0 <= keys.start and keys.stop <= n_k * bk
            met[queries, keys] += 1
        assert (met[:length, :length][visible] == 1).all()


def test_a_window_of_128_is_walked_in_four_tiles_of_256_keys_a_q_block():
    """What ``mimo-sharded4-8k``'s sliding layers run: under 512 x 512 tiles
    a q block touched two masked tiles, an eighth full (31 a head, 12.8%);
    fitted to the band a 128-query chunk meets the 256 keys that end with its
    own, half of which it sees."""
    assert fa._forward_blocks(8192, 8192, 192, 2, None, None) == (512, 8192, 512)
    assert fa._band_span(128, 512, 8192, 512) == 256
    assert fa._band_key_starts(1024, 0, 512, 8192, 256) == [896, 1024, 1152, 1280]
    assert fa._band_key_starts(0, 0, 512, 8192, 256) == [0, 0, 128, 256]
    plain, masked, skipped = fa._count_tiles(8192, 8192, 512, 8192, 512, True, 128)
    assert (plain, masked, skipped) == (0, 64, 64 * 32 - 64)
    visible, computed = fa.band_pairs(8192, 8192, True, 128, d=192)
    assert visible == 128 * 129 // 2 + (8192 - 128) * 128
    assert computed == 64 * 256 * 128 <= 2.5 * visible
    assert 49.5 < 100.0 * visible / computed < 50.0
    # the backward: a 128-key chunk against the 256 queries from its own on
    assert fa._backward_blocks(8192, 8192, None, None) == (512, 512)
    assert fa._band_span(128, 512, 8192, 512) == 256
    assert fa._band_query_starts(0, 1024, 8192, 512, 256) == [1024, 1152, 1280, 1408]
    assert fa._band_query_starts(0, 7680, 8192, 512, 256) == [7680, 7808, 7936, 7936]
    assert fa._count_backward_tiles(16, 8192, 512, 512, True, 128, 256) == \
        (0, 64, 64 * 32 - 64)
    full_visible, full_computed = fa.band_pairs(8192, 8192, True, None, d=192)
    assert full_visible == 8192 * 8193 // 2
    assert full_computed == (16 * 17 // 2) * 512 * 512


@pytest.mark.parametrize("name,length,d,window,blocks,tiles", [
    ("gpt2m", 1024, 64, None, (512, 1024, 512), (1, 2, 1)),
    ("trinity-sliding", 8192, 128, 2048, (512, 8192, 512), (42, 28, 186)),
    ("trinity-full", 8192, 128, None, (512, 8192, 512), (120, 16, 120)),
    ("kanana", 16384, 128, None, (512, 16384, 512), (496, 32, 496)),
    ("mimo-full", 8192, 192, None, (512, 8192, 512), (120, 16, 120)),
], ids=lambda x: x if isinstance(x, str) else "")
def test_the_other_cells_calls_keep_their_blocks_and_their_tiles(
        name, length, d, window, blocks, tiles):
    """No call but a narrow window's is fitted: blocks and tile counts of
    the cells' calls as they were before the fitted walk (653cb21)."""
    assert fa._forward_blocks(length, length, d, 2, None, None) == blocks
    assert fa._backward_blocks(length, length, None, None) == (512, 512)
    assert fa._band_span(window, *blocks) == 0
    assert fa._band_span(window, 512, length, 512) == 0
    assert fa._count_tiles(length, length, *blocks, True, window) == tiles
    assert fa._count_backward_tiles(length // 512, length, 512, 512, True,
                                    window) == tiles


def _rows(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


# name -> (q, k, v as the cell's model hands them, the call's keywords, the
# SHA-256 of the traced call's text at 653cb21)
CELL_CALLS = {
    "gpt2m": ((_rows(8, 1024, 16, 64),) * 3, {},
              "4183f05464f381b1bee317db57e4e37669585aa3628c85a9ef4bd8f6a6c98099"),
    "trinity-sliding": (
        (_rows(1, 8192, 32, 128), _rows(1, 8192, 4, 128), _rows(1, 8192, 512)),
        dict(window=2048, heads=(32, 4)),
        "02d0f686a6693a9664f678c1c330ac1f64023b1e5116c3b00bfdd5e30696b731"),
    "trinity-full": (
        (_rows(1, 8192, 32, 128), _rows(1, 8192, 4, 128), _rows(1, 8192, 512)),
        dict(heads=(32, 4)),
        "cda097de98d1ad6429e77c907a23fa62f1f0f95e5561496725e518afea1c2420"),
    "nemotron": (
        (_rows(1, 8192, 4096), _rows(1, 8192, 256), _rows(1, 8192, 256)),
        dict(heads=(32, 2)),
        "f61ba890b8eecd20f0e88493406bd3e281901ba2c9afade05f1a7258b68ebb71"),
    "kanana": (
        (_rows(1, 16384, 32, 192), _rows(1, 16384, 32 * 256), None),
        dict(heads=(32, 32), k_shared=_rows(1, 16384, 64)),
        "b3db35215ed4e7396ef77309dbe36afc8884743c115ed51a85922ec60f038793"),
}


@pytest.mark.parametrize("name", list(CELL_CALLS))
def test_the_other_cells_calls_trace_to_the_kernels_they_traced_to_before(name):
    """The jaxpr of a cell's call and its gradients AT THE CELL'S SIZE, the
    kernels' bodies included (traced from shapes: nothing is lowered or
    run), by its SHA-256 at the commit before the fitted walk: what the
    eight flash cells that bypass the band compile is the parent's, so their
    set-up is. Regenerate at 653cb21 with this function if the kernels change
    on purpose."""
    (q, k, v), keywords, parent = CELL_CALLS[name]
    keywords = dict(keywords)
    shared = {"k_shared": keywords.pop("k_shared")} if "k_shared" in keywords else {}

    def loss(q, k, v, shared):
        return fa.flash_attention(q, k, v, causal=True, **keywords,
                                  **shared).astype(jnp.float32).sum()

    wrt = (0, 1) + ((2,) if v is not None else ()) + ((3,) if shared else ())
    text = str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=wrt))(
        q, k, v, shared))
    assert hashlib.sha256(text.encode()).hexdigest() == parent


def _lowered(window, sink=False):
    q = jax.ShapeDtypeStruct((1, 1024, 4, 64), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, 1024, 2, 64), jnp.float32)
    args = (q, kv, kv) + ((jax.ShapeDtypeStruct((4,), jnp.float32),)
                          if sink else ())
    return jax.jit(jax.grad(
        lambda q, k, v, s=None: fa.flash_attention(
            q, k, v, causal=True, window=window, sink=s).sum(),
        argnums=(0, 1, 2))).lower(*args).as_text()


@pytest.mark.parametrize("window", list(PARENT_SHA256), ids=lambda w: f"window-{w}")
def test_a_call_without_a_narrow_window_lowers_to_what_it_lowered_to_before(window):
    """The text a grouped call lowers to (interpret mode: plain StableHLO, no
    line numbers) without a window, and under one wider than the sequence or
    exactly a key tile wide, by its SHA-256 at the commit before the fitted
    walk (653cb21; regenerate there with this function if the kernels change
    on purpose): the kernels such a call runs are the parent's. With a sink
    the program differs and names the sink's kernels."""
    plain = _lowered(window)
    assert hashlib.sha256(plain.encode()).hexdigest() == PARENT_SHA256[window]
    assert _lowered(window, sink=True) != plain

