"""Flash attention at two widths (``ops/flash_attention.py``): keys wider
than values, the scale from the key width, and the keys' trailing columns as
one operand all heads share (latent attention's rotary key head), forward and
both backward schedules against the quadratic form; and every call the older
cells make picks the blocks, the schedule and the tiles it picked before.
Kernels in interpret mode on the CPU."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry

fa = importlib.import_module("autodist_tpu.ops.flash_attention")


def _dot_attention(q, k, v, k_shared=None):
    """softmax(q k^T / sqrt(d_qk)) v under the causal mask, float32; the
    shared columns repeated to every head."""
    if k_shared is not None:
        k = jnp.concatenate([k, jnp.broadcast_to(
            k_shared[:, :, None, :], k.shape[:3] + k_shared.shape[-1:])], axis=-1)
    length = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    scores = jnp.where(jnp.tril(jnp.ones((length, length), bool)), scores, -1e9)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


def _operands(length, heads, d_qk, d_v, d_s, dtype=jnp.float32, batch=2):
    keys = jax.random.split(jax.random.PRNGKey(length + d_qk + d_s), 5)
    q = jax.random.normal(keys[0], (batch, length, heads, d_qk), dtype)
    k = jax.random.normal(keys[1], (batch, length, heads, d_qk - d_s), dtype)
    v = jax.random.normal(keys[2], (batch, length, heads, d_v), dtype)
    k_shared = jax.random.normal(keys[3], (batch, length, d_s), dtype) if d_s else None
    g = jax.random.normal(keys[4], (batch, length, heads, d_v), jnp.float32)
    return q, k, v, k_shared, g


# the last column: operands XLA relays around the forward / the backward
# where k and v are handed as rows (q in [B, L, H, D] always). A head's 128
# key columns, v, o, dO, dK and dV then stay where they lie; q and dQ at 192
# do not (odd heads start half a lane tile in), nor an assembled 192-wide k,
# nor anything 96 or 64 wide or padded to a block.
@pytest.mark.parametrize("split", [False, True], ids=["one-pass", "split"])
@pytest.mark.parametrize("length,heads,d_qk,d_v,d_s,relaid", [
    (384, 4, 192, 128, 64, (1, 2)),  # latent attention's widths, the rotary key shared
    (384, 2, 192, 128, 0, (2, 4)),   # the same with k assembled by the caller
    (300, 3, 96, 64, 32, (4, 7)),    # a ragged tail of keys and queries
    (384, 2, 96, 64, 0, (4, 7)),
    (256, 32, 192, 128, 64, (1, 2)),  # the shared key's gradient summed over 32 heads
    (300, 2, 192, 128, 64, (4, 7)),  # latent attention's widths, rows padded
], ids=["192-128-shared", "192-128", "96-64-shared-ragged", "96-64",
        "32-heads-shared", "192-128-shared-ragged"])
def test_two_widths_forward_and_backward_match_dot_attention(
        monkeypatch, length, heads, d_qk, d_v, d_s, relaid, split):
    if split:
        monkeypatch.setattr(fa, "_RESIDENT_DQ_BYTES", 1)
    q, k, v, k_shared, g = _operands(length, heads, d_qk, d_v, d_s,
                                     batch=1 if heads == 32 else 2)
    args = (q, k, v) + ((k_shared,) if d_s else ())

    def flash(*a):
        return fa.flash_attention(a[0], a[1], a[2], q_block=128, k_block=128,
                                  k_shared=a[3] if d_s else None)

    def dot(*a):
        return _dot_attention(a[0], a[1], a[2], a[3] if d_s else None)

    telemetry.registry().clear()
    out = flash(*args)
    assert out.shape == q.shape[:3] + (d_v,)
    np.testing.assert_allclose(out, dot(*args), rtol=2e-5, atol=2e-5)
    wrt = tuple(range(len(args)))
    got = jax.jit(jax.grad(lambda *a: jnp.sum(flash(*a) * g), argnums=wrt))(*args)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(dot(*a) * g), argnums=wrt))(*args)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    gauges = telemetry.snapshot()
    assert (gauges["flash.d_qk"], gauges["flash.d_v"],
            gauges["flash.shared_key_cols"], gauges["flash.kv_group"]) \
        == (d_qk, d_v, d_s, 1)
    assert gauges["flash.bwd.passes"] == (2 if split else 1)
    assert (gauges["flash.fwd.operands_relaid"],
            gauges["flash.bwd.operands_relaid"]) == (4, 7)
    # the same call with k and v handed as rows, the result rows
    rows = lambda x: x.reshape(x.shape[0], length, -1)  # noqa: E731

    def flash_rows(*a):
        return fa.flash_attention(a[0], rows(a[1]), rows(a[2]), q_block=128,
                                  k_block=128, heads=(heads, heads),
                                  k_shared=a[3] if d_s else None)

    out_rows = flash_rows(*args)
    np.testing.assert_array_equal(out_rows, rows(out))
    got_rows = jax.jit(jax.grad(lambda *a: jnp.sum(flash_rows(*a) * rows(g)),
                                argnums=wrt))(*args)
    for a, b in zip(got_rows, got):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-5)
    gauges = telemetry.snapshot()
    assert (gauges["flash.fwd.operands_relaid"],
            gauges["flash.bwd.operands_relaid"]) == relaid


@pytest.mark.parametrize("split", [False, True], ids=["one-pass", "split"])
@pytest.mark.parametrize("length,heads,kv_heads,d_qk,d_v,d_s,relaid", [
    (256, 4, 4, 192, 128, 64, (1, 2)),   # kv_up's output: read where it lies
    (256, 6, 2, 128, 128, 0, (1, 2)),    # grouped heads over a packed pair
    (256, 2, 2, 96, 64, 32, (4, 7)),     # widths that are cut apart by XLA
    (300, 2, 2, 192, 128, 64, (4, 7)),   # rows padded: cut apart as well
], ids=["128+128-shared", "128+128-grouped", "64+64-shared", "128+128-ragged"])
def test_values_packed_behind_the_keys_are_the_two_operands(
        monkeypatch, length, heads, kv_heads, d_qk, d_v, d_s, relaid, split):
    """``flash_attention(q, kv, None)`` with ``kv = [k | v]`` a head, handed
    as one projection's rows, is ``flash_attention(q, k, v)``: the result bit
    for bit, every gradient to the order of one float32 sum, d(kv) back in
    one array; at two equal widths of whole lane tiles the kernels read
    both parts out of the one array (no operand of theirs relaid) and, but
    for a group's, write dK and dV into one."""
    if split:
        monkeypatch.setattr(fa, "_RESIDENT_DQ_BYTES", 1)
    keys = jax.random.split(jax.random.PRNGKey(length + heads), 4)
    d_k = d_qk - d_s
    q = jax.random.normal(keys[0], (2, length, heads, d_qk))
    kv = jax.random.normal(keys[1], (2, length, kv_heads, d_k + d_v))
    k_shared = jax.random.normal(keys[2], (2, length, d_s)) if d_s else None
    g = jax.random.normal(keys[3], (2, length, heads, d_v))

    def packed(q, kv, ks):     # kv as one projection's rows, the result rows
        return fa.flash_attention(
            q, kv.reshape(2, length, -1), None, k_shared=ks, q_block=128,
            k_block=128, heads=(heads, kv_heads)).reshape(2, length, heads, d_v)

    def apart(q, kv, ks):
        return fa.flash_attention(q, kv[..., :d_k], kv[..., d_k:], k_shared=ks,
                                  q_block=128, k_block=128)

    def run(attend):
        wrt = (0, 1, 2) if d_s else (0, 1)
        return (attend(q, kv, k_shared),) + jax.jit(jax.grad(
            lambda *a: jnp.sum(attend(*a) * g), argnums=wrt))(q, kv, k_shared)

    telemetry.registry().clear()
    got = run(packed)
    gauges = telemetry.snapshot()
    assert (gauges["flash.fwd.operands_relaid"],
            gauges["flash.bwd.operands_relaid"]) == relaid
    assert got[2].shape == kv.shape
    want = run(apart)
    np.testing.assert_array_equal(got[0], want[0])
    for a, b in zip(got[1:], want[1:]):     # the order of D's float32 sum apart
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=2e-5)
    want = jax.jit(jax.grad(lambda q, kv, ks: jnp.sum(_dot_attention(
        q, jnp.repeat(kv[..., :d_k], heads // kv_heads, axis=2),
        jnp.repeat(kv[..., d_k:], heads // kv_heads, axis=2), ks) * g),
        argnums=1))(q, kv, k_shared)
    np.testing.assert_allclose(got[2], want, rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError, match="packed"):
        fa.flash_attention(q, kv[..., :d_k], None, k_shared=k_shared)


def test_the_shared_key_in_bfloat16_is_the_assembled_key():
    """Reading the rotary columns through the index map or from 4 copies in
    memory is the same arithmetic but for the order of one float32 sum."""
    q, k, v, k_shared, g = _operands(384, 4, 192, 128, 64, jnp.bfloat16)
    assembled = jnp.concatenate([k, jnp.broadcast_to(
        k_shared[:, :, None, :], (2, 384, 4, 64))], axis=-1)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * g)

    shared = lambda q, k, v, ks: fa.flash_attention(q, k, v, k_shared=ks)  # noqa: E731
    whole = lambda q, k, v: fa.flash_attention(q, k, v)  # noqa: E731
    np.testing.assert_allclose(
        shared(q, k, v, k_shared).astype(jnp.float32),
        whole(q, assembled, v).astype(jnp.float32), rtol=2e-2, atol=2e-2)
    dq, dk, dv, dks = jax.jit(jax.grad(loss(shared), argnums=(0, 1, 2, 3)))(
        q, k, v, k_shared)
    wq, wk, wv = jax.jit(jax.grad(loss(whole), argnums=(0, 1, 2)))(q, assembled, v)
    close = lambda a, b: np.testing.assert_allclose(  # noqa: E731
        a.astype(jnp.float32), b.astype(jnp.float32), rtol=5e-2, atol=5e-2)
    close(dq, wq), close(dk, wk[..., :128]), close(dv, wv)
    assert dks.shape == k_shared.shape and dks.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        dks.astype(jnp.float32),
        wk[..., 128:].astype(jnp.float32).sum(axis=2), rtol=5e-2, atol=0.25)


def test_widths_that_do_not_add_up_are_refused():
    q, k, v, k_shared, _ = _operands(128, 2, 96, 64, 32)
    with pytest.raises(ValueError, match="96 wide, k 64"):
        fa.flash_attention(q, k, v)
    with pytest.raises(ValueError, match=r"k 96 \+ 32 shared"):
        fa.flash_attention(q, jnp.concatenate([k, k[..., :32]], -1), v,
                           k_shared=k_shared)


def _traced_gauges(batch, length, heads, kv_heads, d_qk, d_v=None, d_s=0,
                   window=None):
    """The gauges a forward + backward sets when traced at a shape, nothing
    computed."""
    telemetry.registry().clear()
    shape = lambda h, d: jax.ShapeDtypeStruct(  # noqa: E731
        (batch, length, h, d), jnp.bfloat16)
    operands = [shape(heads, d_qk), shape(kv_heads, d_qk - d_s),
                shape(kv_heads, d_v or d_qk)]
    if d_s:
        operands.append(jax.ShapeDtypeStruct((batch, length, d_s), jnp.bfloat16))
    jax.eval_shape(jax.grad(
        lambda *a: fa.flash_attention(
            a[0], a[1], a[2], window=window,
            k_shared=a[3] if d_s else None).astype(jnp.float32).sum(),
        argnums=tuple(range(len(operands)))), *operands)
    return {k[len("flash."):]: v for k, v in telemetry.snapshot().items()
            if k.startswith("flash.")}


# (B, L, H, H_kv, D, window) of the calls the benchmark's older cells make ->
# the forward's (bq, bk, sub), and (plain, masked, skipped) tiles a head: the
# parent's, which these kernels' second width must not move; and (PR 39) the
# forward's plain tiles whose score product runs under another tile's softmax,
# with K/V of a head resident up to 4 MiB (8,192 rows of 128: Trinity's and
# Nemotron's calls, streamed in 2,048-row blocks until then)
@pytest.mark.parametrize("call,blocks,tiles,group,overlapped", [
    ((8, 1024, 16, 16, 64, None), (512, 1024, 512), (1, 2, 1), 1, 0),   # gpt2m-*
    ((4, 4096, 16, 16, 128, None), (512, 4096, 512), (28, 8, 28), 1, 16),  # olmoe
    ((1, 8192, 32, 4, 128, 2048), (512, 8192, 512), (42, 28, 186), 8, 14),  # trinity, sliding
    ((1, 8192, 32, 4, 128, None), (512, 8192, 512), (120, 16, 120), 8, 80),  # trinity, full
    ((2, 8192, 32, 8, 64, None), (512, 8192, 512), (120, 16, 120), 4, 80),  # lfm2
    ((1, 8192, 32, 2, 128, None), (512, 8192, 512), (120, 16, 120), 16, 80),  # nemotron
], ids=["gpt2m", "olmoe", "trinity-sliding", "trinity-full", "lfm2", "nemotron"])
def test_the_older_cells_calls_pick_what_they_picked(call, blocks, tiles, group,
                                                     overlapped):
    batch, length, heads, kv_heads, d, window = call
    assert fa._forward_blocks(length, length, d, 2, None, None) == blocks
    assert fa._backward_blocks(length, length, None, None) == (512, 512)
    gauges = _traced_gauges(batch, length, heads, kv_heads, d, window=window)
    # (PR 41) operands handed as [B, L, heads, D] go through XLA's transposes
    # as every operand did, whatever their width
    relaid = (4, 7)
    assert gauges == {
        "bwd.passes": 1, "kv_group": group, "window": window or 0,
        "d_qk": d, "d_v": d, "shared_key_cols": 0,
        "fwd.tiles_overlapped": overlapped,
        "fwd.operands_relaid": relaid[0], "bwd.operands_relaid": relaid[1],
        **{f"{side}.tiles_{kind}": n for side in ("fwd", "bwd")
           for kind, n in zip(("plain", "masked", "skipped"), tiles)}}


def test_latent_attentions_call_keeps_its_keys_and_its_schedule_follows_the_width():
    """The float32 dQ of a query is 768 bytes at 192 columns, so the cell's
    16,384 positions are the last the one pass takes (``_RESIDENT_DQ_BYTES``,
    12 MiB since PR 37) where head_dim 128 goes on to 24,576; past PR 29's 4
    MiB the kernel asks for more scoped VMEM, up to it for what the older
    calls ask; the forward keeps the keys of a head in VMEM up to 4 MiB
    (PR 39: the cell's 16,384 rows of 128 are the last; 2,048-row blocks
    until then) and streams the most whole tiles, a power of two of them,
    that 4 MiB hold past it, under the scoped VMEM two buffers of its
    blocks need."""
    assert fa._forward_blocks(16384, 16384, 128, 2, None, None) == (512, 16384, 512)
    assert fa._forward_blocks(16896, 16896, 128, 2, None, None) == (512, 16384, 512)
    assert fa._forward_blocks(32768, 32768, 64, 2, None, None) == (512, 32768, 512)
    assert fa._forward_blocks(32768, 32768, 256, 4, None, None) == (512, 4096, 512)
    assert fa._forward_blocks(16384, 16384, 192, 2, None, None) == (512, 8192, 512)
    assert fa._forward_vmem_limit(1024 * 128 * 2) is None           # gpt2m-*
    assert fa._forward_vmem_limit(4096 * 256 * 2) is None           # olmoe
    assert fa._forward_vmem_limit(8192 * 128 * 2) is None           # lfm2
    assert fa._forward_vmem_limit(8192 * 256 * 2) == 20 << 20       # trinity, nemotron
    assert fa._forward_vmem_limit(16384 * 320 * 2) == 32 << 20      # kanana
    gauges = _traced_gauges(1, 16384, 32, 32, 192, 128, 64)
    assert (gauges["d_qk"], gauges["d_v"], gauges["shared_key_cols"]) == (192, 128, 64)
    assert gauges["bwd.passes"] == 1
    assert (gauges["fwd.tiles_plain"], gauges["fwd.tiles_masked"]) == (496, 32)
    assert (gauges["fwd.operands_relaid"], gauges["bwd.operands_relaid"]) == (4, 7)
    assert _traced_gauges(1, 16896, 4, 4, 192, 128, 64)["bwd.passes"] == 2
    assert _traced_gauges(1, 24576, 4, 4, 128)["bwd.passes"] == 1
    assert _traced_gauges(1, 25088, 4, 4, 128)["bwd.passes"] == 2
    assert fa._backward_vmem_limit(8192 * 128 * 4) == 48 << 20      # the older cells'
    assert fa._backward_vmem_limit(16384 * 64 * 4) == 48 << 20
    assert fa._backward_vmem_limit(16384 * 192 * 4) == 100 << 20
