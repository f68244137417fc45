"""Flash attention under a sliding window and grouped KV heads
(``ops/flash_attention.py`` ``flash_attention(..., window=W)``, K/V with
fewer heads than q): forward and all three gradients against dot attention
with the band mask, in every schedule the kernels have (K/V resident and
walked in tiles, K/V streamed in blocks; the backward in one pass and
split), the walk fitted to a window narrower than a key tile against the
dot path with and without a sink, and the tile counts and gauges against
counts made by hand. Tiny sizes on the CPU; kernels in interpret mode."""

import importlib
import itertools
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry

fa = importlib.import_module("autodist_tpu.ops.flash_attention")

DEPTH = 32


def band_attention(q, k, v, window):
    """Dot attention over [B, L, H, D] with K/V repeated over their group and
    the band ``i - window < j <= i`` as a mask."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    length = q.shape[1]
    i, j = jnp.arange(length)[:, None], jnp.arange(length)[None, :]
    visible = j <= i
    if window is not None:
        visible &= i - j < window
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(visible, scores, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _inputs(length, heads, kv_heads):
    keys = jax.random.split(jax.random.PRNGKey(length + heads), 4)
    shape = lambda h: (1, length, h, DEPTH)  # noqa: E731
    return (jax.random.normal(keys[0], shape(heads)),
            jax.random.normal(keys[1], shape(kv_heads)),
            jax.random.normal(keys[2], shape(kv_heads)),
            jax.random.normal(keys[3], shape(heads)))


# resident: K/V of a head is one block of 1,536 rows (1,100 padded to whole
# 512-key tiles) walked in three tiles, the backward one pass over 512 x 512
# tiles; streamed: 64-row blocks against a length of 200 (ragged), the
# backward one pass; split: the same with the dQ byte limit at 0, so the
# backward is its two kernels and a skipped block is a grid step.
SCHEDULES = {"resident": (1100, None, None, None),
             "streamed": (200, 64, 64, None),
             "split": (200, 64, 64, 0)}


@pytest.mark.parametrize("schedule", sorted(SCHEDULES))
@pytest.mark.parametrize("group", [1, 8], ids=["mha", "kv-group-8"])
@pytest.mark.parametrize("window", [None, 128, 2048],
                         ids=["causal", "window-128", "window-past-L"])
def test_window_and_grouped_heads_match_dot_attention(monkeypatch, window, group,
                                                      schedule):
    length, q_block, k_block, dq_bytes = SCHEDULES[schedule]
    if dq_bytes is not None:
        monkeypatch.setattr(fa, "_RESIDENT_DQ_BYTES", dq_bytes)
    kv_heads = 1 if group == 8 else 2
    q, k, v, w = _inputs(length, kv_heads * group, kv_heads)

    def run(attend):
        loss = lambda q, k, v: jnp.sum(attend(q, k, v) * w)  # noqa: E731
        return attend(q, k, v), jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    out, grads = run(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, window=window, q_block=q_block, k_block=k_block))
    ref_out, ref_grads = run(lambda q, k, v: band_attention(q, k, v, window))
    assert telemetry.gauge("flash.window").value == (window or 0)
    assert telemetry.gauge("flash.kv_group").value == group
    assert telemetry.gauge("flash.bwd.passes").value == \
        (2 if schedule == "split" else 1)
    np.testing.assert_allclose(out, ref_out, atol=2e-5)
    for name, got, want in zip("qkv", grads, ref_grads):
        assert got.shape == want.shape, name
        np.testing.assert_allclose(got, want, atol=2e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("schedule,length,q_block,k_block,dq_bytes", [
    ("resident", 1024, None, None, None), ("streamed", 512, 128, 128, None),
    ("split", 512, 128, 128, 0)], ids=lambda x: x if isinstance(x, str) else "")
@pytest.mark.parametrize("window", [None, 200], ids=["causal", "window-200"])
def test_grouped_heads_of_whole_lane_tiles_are_read_in_place(
        monkeypatch, window, schedule, length, q_block, k_block, dq_bytes):
    """Heads 128 wide handed as rows, no row padded: q, k, v, o and dQ stay
    ``[B, L, heads * 128]`` (``operands_relaid`` 0 / 0), a query head finds
    its KV head's columns through the index map, and a group's dK / dV,
    summed over the group, come back as rows too."""
    if dq_bytes is not None:
        monkeypatch.setattr(fa, "_RESIDENT_DQ_BYTES", dq_bytes)
    monkeypatch.setattr(sys.modules[__name__], "DEPTH", 128)
    operands = _inputs(length, 6, 2)
    rows = [x.reshape(1, length, -1) for x in operands]

    def run(attend, q, k, v, w):
        loss = lambda q, k, v: jnp.sum(attend(q, k, v) * w)  # noqa: E731
        return (attend(q, k, v),) + jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    got = run(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, window=window, q_block=q_block, k_block=k_block,
        heads=(6, 2)), *rows)
    assert (telemetry.gauge("flash.fwd.operands_relaid").value,
            telemetry.gauge("flash.bwd.operands_relaid").value) == (0, 0)
    assert telemetry.gauge("flash.kv_group").value == 3
    want = run(lambda q, k, v: band_attention(q, k, v, window), *operands)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        assert a.shape == (1, length, b.shape[2] * 128), name
        np.testing.assert_allclose(a.reshape(b.shape), b, atol=2e-4, err_msg=name)


def test_a_window_of_one_key_returns_v():
    q, k, v, _ = _inputs(256, 2, 2)
    out = fa.flash_attention(q, k, v, window=1, q_block=64, k_block=64)
    np.testing.assert_allclose(out, v, atol=1e-6)


def test_window_needs_a_causal_mask_and_heads_that_divide():
    q, k, v, _ = _inputs(64, 4, 2)
    with pytest.raises(ValueError, match="causal"):
        fa.flash_attention(q, k, v, causal=False, window=16)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, k, v, window=0)
    with pytest.raises(ValueError, match="KV heads"):
        fa.flash_attention(q[:, :, :3], k, v)


# ---------------------------------------------------------- the fitted walk

# Under a window narrower than a key tile (512) the default blocks run the
# walk fitted to the band (``_band_span``): every window of the list on each
# side of a lane tile, lengths that are one q block, two with a ragged second,
# and four whole ones. The forms take turns (seven of them over the 28 pairs,
# so every length meets every form and every window four of them): a sink or
# none, 8 query heads over one KV head, keys 192 over values 128, bfloat16
# operands, and the backward as its two kernels (which keep the tiles' own
# walk; the forward is fitted all the same).
FITTED_WINDOWS = (1, 64, 100, 128, 200, 256, 511)
FITTED_LENGTHS = (256, 640, 1000, 2048)
FITTED_FORMS = (
    dict(sink=True, group=1, widths=(32, 32), dtype="float32", split=False),
    dict(sink=False, group=8, widths=(32, 32), dtype="float32", split=False),
    dict(sink=True, group=1, widths=(192, 128), dtype="float32", split=False),
    dict(sink=False, group=1, widths=(32, 32), dtype="bfloat16", split=False),
    dict(sink=True, group=1, widths=(32, 32), dtype="float32", split=True),
    dict(sink=True, group=8, widths=(192, 128), dtype="bfloat16", split=False),
    dict(sink=False, group=1, widths=(128, 128), dtype="float32", split=True),
)


def _fitted_cases():
    pairs = itertools.product(FITTED_WINDOWS, FITTED_LENGTHS)
    return [(window, length, FITTED_FORMS[i % len(FITTED_FORMS)])
            for i, (window, length) in enumerate(pairs)]


def _fitted_id(x):
    if not isinstance(x, dict):
        return str(x)
    return "-".join([f"g{x['group']}", "x".join(map(str, x["widths"])),
                     x["dtype"]] + ["sink"] * x["sink"] + ["split"] * x["split"])


@pytest.mark.parametrize("window,length,form", _fitted_cases(), ids=_fitted_id)
def test_the_walk_fitted_to_a_narrow_band_matches_the_dot_path(
        monkeypatch, window, length, form):
    """Forward and the gradients of q, k, v and the sink against
    ``sink_dot_attention`` on the same operands in float32."""
    from autodist_tpu.models.mimo_v2 import sink_dot_attention
    if form["split"]:
        monkeypatch.setattr(fa, "_RESIDENT_DQ_BYTES", 0)
    (d_qk, d_v), dtype = form["widths"], jnp.dtype(form["dtype"])
    heads = form["group"]
    keys = jax.random.split(jax.random.PRNGKey(window + length), 5)
    q = jax.random.normal(keys[0], (1, length, heads, d_qk)).astype(dtype)
    k = jax.random.normal(keys[1], (1, length, 1, d_qk)).astype(dtype)
    v = jax.random.normal(keys[2], (1, length, 1, d_v)).astype(dtype)
    sink = 2.0 * jax.random.normal(keys[3], (heads,)) if form["sink"] else None
    w = jax.random.normal(keys[4], (1, length, heads, d_v))
    args = (q, k, v) + ((sink,) if form["sink"] else ())

    def run(attend):
        def loss(q, k, v, sink=None):
            out = attend(q, k, v, sink).astype(jnp.float32)
            return jnp.sum(out * w), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(len(args))), has_aux=True))(*args)
        return (out, *grads)

    got = run(lambda q, k, v, s: fa.flash_attention(
        q, k, v, causal=True, window=window, sink=s))
    bq, bk, sub = fa._forward_blocks(length, length, max(d_qk, d_v),
                                     dtype.itemsize, None, None)
    # one q block and one key tile of 256 at that length: a window that
    # reaches all of it keeps the tile's own walk
    fitted = window < sub
    assert (fa._band_span(window, bq, bk, sub) > 0) == fitted
    if fitted:
        assert telemetry.gauge("flash.fwd.tiles_plain").value == 0
        assert telemetry.gauge("flash.fwd.tiles_masked").value == \
            -(-length // bq) * (bq // 128)
    assert telemetry.gauge("flash.bwd.passes").value == (2 if form["split"] else 1)
    want = run(lambda q, k, v, s: sink_dot_attention(
        *(x.astype(jnp.float32) for x in (q, k, v)), window, s, jnp.float32))
    # float32: summation order alone; bfloat16: p and dS are rounded (2^-8)
    # where they enter a product, the sums are float32
    atol = 3e-5 if dtype == jnp.float32 else 3e-2
    for name, a, b in zip(("out", "dq", "dk", "dv", "dsink"), got, want):
        assert a.shape == b.shape, name
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        np.testing.assert_allclose(a.astype(jnp.float32) / scale, b / scale,
                                   atol=atol, err_msg=name)


# ------------------------------------------------------------- tile counts

def _by_hand(n_q, tile, window):
    """(plain, masked, skipped) [tile x tile] score tiles of an n_q x n_q
    square under the band, counted pair by pair from the positions."""
    plain = masked = 0
    for qi in range(n_q):
        for ki in range(n_q):
            rows = np.arange(qi * tile, (qi + 1) * tile)[:, None]
            keys = np.arange(ki * tile, (ki + 1) * tile)[None, :]
            visible = (keys <= rows) & (rows - keys < window)
            plain += visible.all()
            masked += visible.any() and not visible.all()
    return int(plain), int(masked), n_q * n_q - int(plain) - int(masked)


@pytest.mark.parametrize("length,tile,window,want", [
    # the trinity cell's call: per q block the tile the lower edge crosses,
    # three plain ones, the diagonal's; the first four blocks have no lower edge
    (8192, 512, 2048, (42, 28, 186)),
    (256, 64, 128, (3, 6, 7)),
    (256, 64, 64, (0, 7, 9)),          # a window of one tile: both edges cross
    (256, 64, 4096, (6, 4, 6)),        # wider than the sequence: the triangle
], ids=["trinity-8k", "two-tiles", "one-tile", "past-L"])
def test_tile_counts_under_the_band_equal_a_hand_count(length, tile, window, want):
    n_q = length // tile
    assert _by_hand(n_q, tile, window) == want
    # the forward's walk: streamed K/V blocks of four tiles, and one resident block
    assert fa._count_tiles(length, length, tile, 4 * tile, tile, True, window) == want
    assert fa._count_tiles(length, length, tile, length, tile, True, window) == want
    # the backward's walk over q tiles, a K/V block a tile
    assert fa._count_backward_tiles(n_q, length, tile, tile, True, window) == want
    if window >= length:
        assert fa._count_tiles(length, length, tile, length, tile, True) == want
        assert fa._count_backward_tiles(n_q, length, tile, tile, True) == want


def test_the_gauges_count_the_band():
    q, k, v, w = _inputs(256, 2, 1)
    jax.jit(jax.grad(lambda q: jnp.sum(fa.flash_attention(
        q, k, v, window=128, q_block=64, k_block=64) * w)))(q)
    for pass_ in ("fwd", "bwd"):
        got = tuple(telemetry.gauge(f"flash.{pass_}.tiles_{name}").value
                    for name in ("plain", "masked", "skipped"))
        assert got == (3, 6, 7), pass_


def test_ragged_keys_under_a_window_are_counted_masked_not_plain():
    """200 keys in 64-row blocks: the last block holds 8 real keys, so every
    tile that meets it is masked whatever the band says."""
    plain, masked, skipped = fa._count_tiles(200, 200, 64, 64, 64, True, 100)
    # q block 0: diagonal. 1: [0,64) crossed below (key 0 is 64 back of query
    # 64.. and out of reach of query 100+), diagonal. 2: tile 0 crossed, tile 1
    # crossed (query 191 sees from key 92), diagonal. 3: tile 0 below every
    # query (192 - 100 = 92 > 63), tile 1 and 2 crossed, the ragged diagonal.
    assert (plain, masked, skipped) == (0, 9, 7)
    assert fa._count_backward_tiles(4, 200, 64, 64, True, 100) == (0, 9, 7)
