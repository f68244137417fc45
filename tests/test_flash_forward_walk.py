"""The flash forward's walk over key tiles (``ops/flash_attention.py``
``_walk``, ``_attend_block``): plain tiles in straight-line blocks whose
score products are issued ahead of the softmaxes they run under, everything
else one tile at a time. The order of a walk against the parent's (every
needed tile once, ascending, with its class) for every shape of the three
ranges, static and traced; the interpreted kernel against float32 dot
attention in every form a cell calls it in, at sizes where the blocks fire;
the gradient through it; and the gauge that counts the overlapped tiles.
Tiny sizes on the CPU; kernels in interpret mode."""

import importlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry

fa = importlib.import_module("autodist_tpu.ops.flash_attention")

N_TILES = 11
ALONE, IN_BLOCK = 0, 2      # how a tile ran; + 1: under the masked body


def _callbacks():
    """``tile`` and ``group`` that log ``(tile, how)`` in the order the
    updates happen; they work on ints and under a trace alike."""
    def tile(j, state, masked):
        pos, log = state
        return pos + 1, log.at[pos].set(jnp.stack(
            [jnp.asarray(j, jnp.int32), jnp.int32(ALONE + masked)]))

    def group(first, state, size):
        pos, log = state
        for u in range(size):
            log = log.at[pos + u].set(jnp.stack(
                [jnp.asarray(first + u, jnp.int32), jnp.int32(IN_BLOCK)]))
        return pos + size, log

    return tile, group


def _logged_walk(counts, groups):
    return fa._walk(tuple(counts), groups, *_callbacks(),
                    (jnp.int32(0), jnp.full((N_TILES + 1, 2), -1, jnp.int32)))


def _ranges():
    """(n_lo, n_ps, n_pe, n_need) with each of the three ranges empty, one
    tile or many, and a skipped head and tail of each kind."""
    lengths = (0, 1, 2, 5)
    for skipped, low, plain, high in itertools.product((0, 1), lengths,
                                                       (0, 1, 2, 3, 4, 7),
                                                       (0, 1, 2)):
        n_lo = skipped
        n_need = n_lo + low + plain + high
        if n_need <= N_TILES:
            yield n_lo, n_lo + low, n_lo + low + plain, n_need


@pytest.mark.parametrize("groups", [(), (2,), (4, 2), (8, 4, 2)],
                         ids=["chain", "pairs", "fours-and-pairs", "eights"])
@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
def test_a_walk_visits_every_needed_tile_once_in_the_parents_order(groups, traced):
    run = jax.jit(lambda counts: _logged_walk(counts, groups)) if traced \
        else lambda counts: _logged_walk([int(n) for n in counts], groups)

    def walk(counts, groups):
        pos, log = run(jnp.asarray(counts, jnp.int32))
        return int(pos), np.asarray(log)

    cases = 0
    for counts in _ranges():
        n_lo, n_ps, n_pe, n_need = counts
        pos, log = walk(counts, groups)
        # the parent's order: ascending, each tile once, nothing after it
        assert pos == n_need - n_lo, counts
        assert list(log[:pos, 0]) == list(range(n_lo, n_need)), counts
        assert (log[pos:] == -1).all(), counts      # and the walk has drained
        for j, how in log[:pos]:
            plain = n_ps <= j < n_pe
            assert (how in (ALONE, IN_BLOCK)) == plain, (counts, j, how)
        in_blocks = int((log[:pos, 1] == IN_BLOCK).sum())
        # all but the first of every block runs under a neighbour
        left, blocks = n_pe - n_ps, 0
        for size in groups:
            blocks, left = blocks + left // size, left % size
        assert in_blocks == (n_pe - n_ps) - left, counts
        assert fa._grouped(n_pe - n_ps, groups) == in_blocks - blocks, counts
        cases += 1
    assert cases > 100


def test_the_blocks_are_those_a_run_can_fill():
    assert fa._WALK_GROUPS == (4, 2)
    assert [fa._walk_groups(run) for run in (0, 1, 2, 3, 4, 9)] == \
        [(), (), (2,), (2,), (4, 2), (4, 2)]
    assert [fa._grouped(run, (4, 2)) for run in range(9)] == \
        [0, 0, 1, 1, 3, 3, 4, 4, 6]


def _dot_attention(q, k, v, causal=True, window=None, k_shared=None):
    """float32 softmax(q k^T / sqrt(d)) v and its log-sum-exp, K/V repeated
    over their group, the shared columns over every head."""
    if k_shared is not None:
        k = jnp.concatenate([k, jnp.broadcast_to(
            k_shared[:, :, None, :], k.shape[:3] + k_shared.shape[-1:])], axis=-1)
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    lq, lk = q.shape[1], k.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision="highest") / np.sqrt(q.shape[-1])
    i, j = jnp.arange(lq)[:, None], jnp.arange(lk)[None, :]
    visible = jnp.ones((lq, lk), bool)
    if causal:
        visible &= j <= i
    if window is not None:
        visible &= i - j < window
    scores = jnp.where(visible, scores, -jnp.inf)
    lse = jax.nn.logsumexp(scores, axis=-1)                 # [b, h, q]
    out = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(scores - lse[..., None]), v,
                     precision="highest")
    return out, lse


# name -> (L, H, H_kv, D_qk, D_v, D_shared, causal, window, q_block, k_block):
# each cell's form at a size where its walks hold blocks of plain tiles.
# Keys of 640 / 768 rows a block are walked in 128-key tiles; K/V resident
# past 512 rows is walked in 512-key tiles.
FORMS = {
    # gpt2m / lfm2: width 64, K/V of a head resident (3,072 rows: 6 tiles)
    "resident-64": (3072, 2, 2, 64, 64, 0, True, None, None, None),
    # olmoe / nemotron / trinity's full layer: width 128, streamed, grouped
    "streamed-128-grouped": (1920, 4, 1, 128, 128, 0, True, None, 128, 640),
    # trinity's sliding layers: a window, the band's lower edge in the walk
    "window": (1920, 4, 2, 128, 128, 0, True, 700, 128, 640),
    # kanana: keys 192 = 128 + 64 shared over values 128
    "shared-192-128": (1536, 2, 2, 192, 128, 64, True, None, 128, 768),
    # a ragged tail of keys and queries
    "ragged": (1700, 2, 2, 64, 64, 0, True, None, 128, 640),
    "noncausal": (1280, 2, 2, 64, 64, 0, False, None, 128, 640),
}


def _form_operands(length, h, h_kv, d_qk, d_v, d_s, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(length + d_qk + h), 5)
    q = jax.random.normal(keys[0], (1, length, h, d_qk), dtype)
    k = jax.random.normal(keys[1], (1, length, h_kv, d_qk - d_s), dtype)
    v = jax.random.normal(keys[2], (1, length, h_kv, d_v), dtype)
    k_shared = jax.random.normal(keys[3], (1, length, d_s), dtype) if d_s else None
    g = jax.random.normal(keys[4], (1, length, h, d_v), jnp.float32)
    return q, k, v, k_shared, g


@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_interpreted_kernel_matches_dot_attention_where_the_blocks_fire(form):
    length, h, h_kv, d_qk, d_v, d_s, causal, window, q_block, k_block = FORMS[form]
    q, k, v, k_shared, _ = _form_operands(length, h, h_kv, d_qk, d_v, d_s)
    out, lse = fa._flash_forward(q, k, v, causal, q_block, k_block, True,
                                 window, k_shared)
    assert telemetry.gauge("flash.fwd.tiles_overlapped").value > 0
    want, want_lse = _dot_attention(q, k, v, causal, window, k_shared)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    got_lse = lse.reshape(h, -1)[:, :length]
    np.testing.assert_allclose(got_lse, want_lse[0], atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_blocks_change_no_value(monkeypatch, form):
    """The same call with no block (the one-tile chain for every tile, the
    parent's kernel) gives the same numbers: a block reorders when a product
    is issued, not what is added to what."""
    length, h, h_kv, d_qk, d_v, d_s, causal, window, q_block, k_block = FORMS[form]
    q, k, v, k_shared, _ = _form_operands(length, h, h_kv, d_qk, d_v, d_s)
    args = (q, k, v, causal, q_block, k_block, True, window, k_shared)
    out, lse = fa._flash_forward(*args)
    monkeypatch.setattr(fa, "_WALK_GROUPS", ())
    chain_out, chain_lse = fa._flash_forward(*args)
    assert telemetry.gauge("flash.fwd.tiles_overlapped").value == 0
    # the interpreter's products over half the queries may sum in another
    # order than over all of them: float32 rounding, nothing more
    np.testing.assert_allclose(out, chain_out, atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(lse, chain_lse, atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("where", ["diagonal", "visible"])
def test_the_carry_step_under_traced_offsets_matches_blockwise(where):
    """Ring attention's local step with K/V blocks of five tiles: on the
    diagonal the classes are decided at run time, wholly visible every tile
    is plain and the walk is a block of four and one alone."""
    from autodist_tpu.ops.blockwise_attention import blockwise_attention_with_carry

    length, h, d = 640, 2, 64
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (jax.random.normal(key, (1, length, h, d)) for key in keys)
    q_offset = length
    k_offset = length if where == "diagonal" else 0

    @jax.jit
    def flash(q, k, v, q_off, k_off):
        return fa.flash_attention_with_carry(
            q, k, v, None, causal=True, q_offset=q_off, k_offset=k_off,
            q_block=128, k_block=640)

    got = flash(q, k, v, jnp.int32(q_offset), jnp.int32(k_offset))
    want = blockwise_attention_with_carry(
        q, k, v, None, causal=True, q_offset=q_offset, k_offset=k_offset,
        block_size=128)
    for a, b, name in zip(got, want, ("acc", "m", "l")):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=2e-5, err_msg=name)


@pytest.mark.parametrize("form", ["resident-64", "streamed-128-grouped",
                                  "window", "shared-192-128"])
def test_the_gradient_through_the_forward_is_the_dot_forms(form):
    """The backward reads the forward's ``o`` and lse: to the tolerances the
    older tests hold the gradients to."""
    length, h, h_kv, d_qk, d_v, d_s, causal, window, q_block, k_block = FORMS[form]
    length = min(length, 1536)
    q, k, v, k_shared, g = _form_operands(length, h, h_kv, d_qk, d_v, d_s)
    operands = (q, k, v) + ((k_shared,) if d_s else ())

    def flash(*a):
        return jnp.sum(g * fa.flash_attention(
            a[0], a[1], a[2], causal=causal, window=window,
            k_shared=a[3] if d_s else None, q_block=q_block, k_block=k_block))

    def dot(*a):
        return jnp.sum(g * _dot_attention(a[0], a[1], a[2], causal, window,
                                          a[3] if d_s else None)[0])

    argnums = tuple(range(len(operands)))
    got = jax.jit(jax.grad(flash, argnums))(*operands)
    assert telemetry.gauge("flash.fwd.tiles_overlapped").value > 0
    want = jax.jit(jax.grad(dot, argnums))(*operands)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


def _forward_gauges(batch, length, heads, kv_heads, d_qk, d_v=None, d_s=0,
                    window=None):
    """The forward's gauges at a call's shape, traced and not run."""
    d_v = d_v or d_qk
    shapes = [jax.ShapeDtypeStruct((batch, length, heads, d_qk), jnp.bfloat16),
              jax.ShapeDtypeStruct((batch, length, kv_heads, d_qk - d_s), jnp.bfloat16),
              jax.ShapeDtypeStruct((batch, length, kv_heads, d_v), jnp.bfloat16)]
    if d_s:
        shapes.append(jax.ShapeDtypeStruct((batch, length, d_s), jnp.bfloat16))
    jax.eval_shape(lambda q, k, v, ks=None: fa._flash_forward(
        q, k, v, True, None, None, True, window, ks), *shapes)
    return {k[len("flash.fwd."):]: v for k, v in telemetry.snapshot().items()
            if k.startswith("flash.fwd.")}


# the cells' calls -> (plain, masked, overlapped) tiles of one (batch, head),
# and the blocks the kernel is built with
@pytest.mark.parametrize("call,tiles,groups", [
    # kanana: 112 walks of 4 plain tiles (3 each under a neighbour), and the
    # diagonal's 0-3 plain tiles before its masked one (a pair in 16 walks)
    (dict(batch=1, length=16384, heads=32, kv_heads=32, d_qk=192, d_v=128, d_s=64),
     (496, 32, 352), (4, 2)),
    # trinity's full layer, and nemotron's call: 24 walks of 4, 8 pairs
    (dict(batch=1, length=8192, heads=32, kv_heads=4, d_qk=128), (120, 16, 80), (4, 2)),
    # trinity's sliding layers: never more than 3 plain tiles in a walk
    (dict(batch=1, length=8192, heads=32, kv_heads=4, d_qk=128, window=2048),
     (42, 28, 14), (2,)),
    # lfm2: K/V resident, 16 walks of 0-15 plain tiles
    (dict(batch=2, length=8192, heads=32, kv_heads=8, d_qk=64), (120, 16, 80), (4, 2)),
    # olmoe: resident, 8 walks of 0-7
    (dict(batch=4, length=4096, heads=16, kv_heads=16, d_qk=128), (28, 8, 16), (4, 2)),
    # gpt2m: walks of one and two tiles hold one plain tile at most: the
    # parent's kernel, no block in it
    (dict(batch=8, length=1024, heads=16, kv_heads=16, d_qk=64), (1, 2, 0), ()),
], ids=["kanana", "trinity-full", "trinity-sliding", "lfm2", "olmoe", "gpt2m"])
def test_the_gauge_counts_the_tiles_under_a_neighbours_softmax(call, tiles, groups):
    gauges = _forward_gauges(**call)
    assert (gauges["tiles_plain"], gauges["tiles_masked"],
            gauges["tiles_overlapped"]) == tiles
    length, d = call["length"], max(call["d_qk"] - call.get("d_s", 0),
                                    call.get("d_v") or 0)
    bq, bk, sub = fa._forward_blocks(length, length, d, 2, None, None)
    runs = [run for _, run in fa._walks(length, length, bq, bk, sub, True,
                                        call.get("window"))]
    assert fa._walk_groups(max(runs)) == groups
