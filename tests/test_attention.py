"""Attention stack: blockwise == plain softmax; flash kernel == blockwise;
ring attention over the seq axis == single-device attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from autodist_tpu import const
from autodist_tpu.models.transformer_lm import causal_mask, dot_product_attention
from autodist_tpu.ops.blockwise_attention import blockwise_attention
from autodist_tpu.ops.flash_attention import flash_attention
from autodist_tpu.parallel.mesh import build_mesh
from autodist_tpu.parallel.ring_attention import ring_attention

B, L, H, D = 2, 64, 4, 16

# Ring/sequence-parallel cases shard over an 8-way mesh; a single real chip
# can't host them (the CPU-sim suite provides 8 virtual devices).
_NEEDS_MESH = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs an 8-device mesh (run under the CPU-sim suite)")


def _close(a, b, atol, rtol=1e-7, mxu=0.01, **kw):
    """Backend-aware comparison: exact-ish on the CPU suite (deterministic
    orderings); on Mosaic-compiling backends both sides run matmuls at MXU
    (bf16-pass) precision with different orderings, so two correct
    implementations legitimately differ at MXU bf16-pass resolution —
    bounded at ``mxu`` (1e-2 for normalized outputs; gradient and raw
    carry-state comparisons pass 5e-2 — the backward chains two more matmuls
    and the unnormalized accumulators run at larger magnitudes)."""
    if jax.default_backend() == "tpu":
        atol, rtol = max(atol, mxu), max(rtol, mxu)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol,
                               atol=atol, **kw)


def _qkv(seed=0, l=L):
    rng = np.random.RandomState(seed)
    shape = (B, l, H, D)
    return (jnp.asarray(rng.randn(*shape), jnp.float32),
            jnp.asarray(rng.randn(*shape), jnp.float32),
            jnp.asarray(rng.randn(*shape), jnp.float32))


def _reference(q, k, v, causal=True):
    mask = causal_mask(q.shape[1], jnp.float32) if causal else jnp.zeros(())
    return dot_product_attention(q, k, v, mask, jnp.float32)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block", [16, 17, 64, 256])
def test_blockwise_matches_reference(causal, block):
    q, k, v = _qkv()
    want = _reference(q, k, v, causal)
    got = blockwise_attention(q, k, v, causal=causal, block_size=block)
    _close(got, want, atol=2e-5)


def test_blockwise_gradients_match_reference():
    q, k, v = _qkv(1)

    def f_ref(q, k, v):
        return jnp.sum(_reference(q, k, v) ** 2)

    def f_blk(q, k, v):
        return jnp.sum(blockwise_attention(q, k, v, block_size=16) ** 2)

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    g_blk = jax.grad(f_blk, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_blk):
        _close(a, b, atol=3e-4, mxu=0.05)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernel_matches_reference(causal):
    q, k, v = _qkv(2)
    want = _reference(q, k, v, causal)
    got = flash_attention(q, k, v, causal=causal, q_block=32, k_block=32)
    _close(got, want, atol=2e-5)


def test_flash_kernel_ragged_length():
    # L=60 not divisible by the 32-blocks: padding must not leak into results.
    q, k, v = _qkv(3, l=60)
    want = _reference(q, k, v, True)
    got = flash_attention(q, k, v, causal=True, q_block=32, k_block=32)
    _close(got, want, atol=2e-5)


def test_flash_gradients_flow():
    q, k, v = _qkv(4)

    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, q_block=32, k_block=32) ** 2)

    grads = jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    def f_ref(q, k, v):
        return jnp.sum(_reference(q, k, v) ** 2)

    want = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads, want):
        _close(a, b, atol=3e-4, mxu=0.05)


# (length, causal, q_block, k_block): which classes of score tile the forward
# meets — plain (below the diagonal, no padded key), masked (crossed by the
# diagonal or holding the ragged tail), skipped (above the diagonal or all
# padding). A K/V block is walked in key tiles of 512/256/128 where one divides it.
_BLOCK_CLASS_CASES = {
    "causal-aligned": (64, True, 32, 32),            # masked diagonal, plain below, skipped above
    "noncausal-aligned": (64, False, 32, 32),        # plain only
    "causal-ragged": (60, True, 32, 32),             # the tail and the diagonal in one tile
    "noncausal-ragged": (60, False, 32, 32),         # masked by the tail alone
    "q-block-below-k-block": (128, True, 32, 64),
    "q-block-above-k-block": (128, True, 64, 32),
    "tiles-inside-a-resident-block": (384, True, 128, 384),   # 3 plain, 3 masked, 3 skipped tiles of 128 keys
    "ragged-inside-a-resident-block": (300, False, 128, 256),  # second block: 44 real keys, then a tile of padding
    "blocks-from-the-shape": (640, True, None, None),          # 512-row q blocks, K/V resident, padded to 1,024
}


@pytest.mark.parametrize("case", list(_BLOCK_CLASS_CASES))
def test_flash_block_classes_match_reference(case):
    """Forward and gradients against plain softmax attention, one case per
    mix of tile classes."""
    length, causal, q_block, k_block = _BLOCK_CLASS_CASES[case]
    rng = np.random.RandomState(7)
    q, k, v = (jnp.asarray(rng.randn(1, length, 2, 16), jnp.float32)
               for _ in range(3))
    weights = jnp.asarray(rng.randn(1, length, 2, 16), jnp.float32)

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v) * weights)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, q_block=q_block,
                               k_block=k_block)

    def plain(q, k, v):
        return _reference(q, k, v, causal)

    _close(flash(q, k, v), plain(q, k, v), atol=2e-5)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        _close(a, b, atol=3e-4, mxu=0.05, err_msg=f"d{name}")


@pytest.mark.parametrize("length,causal,want", [
    (1024, True, (1, 2, 1)),    # 512-row q blocks x 512-key tiles: the diagonal crosses two
    (300, True, (0, 1, 0)),     # ragged, one block, one tile
    (1024, False, (4, 0, 0)),
    (600, False, (2, 2, 0)),    # K/V padded to 1,024: each q block's second tile holds the tail
    (2048, True, (6, 4, 6)),
], ids=["L1024-causal", "L300-ragged", "L1024-noncausal", "L600-noncausal-ragged",
        "L2048-causal"])
def test_flash_forward_tile_gauges_equal_hand_count(length, causal, want):
    """``flash.fwd.tiles_*``: score tiles of one (batch, head) by class, set
    when the forward is traced."""
    from autodist_tpu import telemetry

    qkv = jax.ShapeDtypeStruct((1, length, 2, 64), jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: flash_attention(q, k, v, causal=causal),
                   qkv, qkv, qkv)
    got = tuple(telemetry.gauge(f"flash.fwd.tiles_{name}").value
                for name in ("plain", "masked", "skipped"))
    assert got == want


def test_kernel_names_unchanged():
    from autodist_tpu.ops import named_call
    assert named_call.KERNEL_NAMES == (
        "flash_fwd", "flash_bwd_dkv", "flash_bwd_dq", "flash_carry",
        "xent_fwd", "xent_bwd_dh", "xent_bwd_dw",
        "moe_gmm_fwd", "moe_gmm_bwd_dx", "moe_gmm_bwd_dw")


@_NEEDS_MESH
@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_single_device(causal):
    """Sequence sharded over a 4-way seq axis: ring result == full attention."""
    mesh = build_mesh(axes={const.MESH_AXIS_SEQ: 4, const.MESH_AXIS_DATA: 2})
    q, k, v = _qkv(5)
    want = _reference(q, k, v, causal)

    spec = P(const.MESH_AXIS_DATA, const.MESH_AXIS_SEQ, None, None)
    fn = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, causal=causal, block_size=16),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    got = fn(q, k, v)
    _close(got, want, atol=2e-5)


@_NEEDS_MESH
def test_ring_attention_gradients_flow():
    mesh = build_mesh(axes={const.MESH_AXIS_SEQ: 4, const.MESH_AXIS_DATA: 2})
    q, k, v = _qkv(6)
    spec = P(const.MESH_AXIS_DATA, const.MESH_AXIS_SEQ, None, None)
    fn = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, causal=True, block_size=16),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)

    def loss(q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def loss_ref(q, k, v):
        return jnp.sum(_reference(q, k, v) ** 2)

    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads, want):
        _close(a, b, atol=3e-4, mxu=0.05)


def test_transformer_with_flash_attention_matches_dot():
    import dataclasses
    from autodist_tpu.models import transformer_lm
    cfg = transformer_lm.TransformerLMConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64, max_len=64,
        dtype=jnp.float32)
    model, params = transformer_lm.init_params(cfg)
    batch = transformer_lm.synthetic_batch(cfg, batch_size=4, seq_len=32)
    loss_dot = transformer_lm.make_loss_fn(model)(params, batch)
    cfg_flash = dataclasses.replace(cfg, attention_impl="flash")
    model_flash = transformer_lm.TransformerLM(cfg_flash)
    loss_flash = transformer_lm.make_loss_fn(model_flash)(params, batch)
    _close(float(loss_dot), float(loss_flash), atol=0, rtol=1e-5)


def test_transformer_with_blockwise_attention_matches_dot():
    """attention_impl='blockwise' (the O(L)-memory pure-JAX path the
    long-context example uses off-Mosaic) is value-identical to dot."""
    import dataclasses
    from autodist_tpu.models import transformer_lm
    cfg = transformer_lm.TransformerLMConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64, max_len=64,
        dtype=jnp.float32)
    model, params = transformer_lm.init_params(cfg)
    batch = transformer_lm.synthetic_batch(cfg, batch_size=4, seq_len=32)
    loss_dot = transformer_lm.make_loss_fn(model)(params, batch)
    cfg_bw = dataclasses.replace(cfg, attention_impl="blockwise")
    model_bw = transformer_lm.TransformerLM(cfg_bw)
    loss_bw = transformer_lm.make_loss_fn(model_bw)(params, batch)
    _close(float(loss_dot), float(loss_bw), atol=0, rtol=1e-5)


def test_flash_carry_matches_blockwise_carry():
    """The pallas carry variant and the pure-JAX carry produce the same
    (acc, m, l) state, including with offsets and a carry-in (the ring step)."""
    from autodist_tpu.ops.blockwise_attention import blockwise_attention_with_carry
    from autodist_tpu.ops.flash_attention import flash_attention_with_carry

    rng = np.random.RandomState(0)
    b, l, h, d = 2, 32, 2, 8
    q = jnp.asarray(rng.randn(b, l, h, d), jnp.float32)
    k1 = jnp.asarray(rng.randn(b, l, h, d), jnp.float32)
    v1 = jnp.asarray(rng.randn(b, l, h, d), jnp.float32)
    k2 = jnp.asarray(rng.randn(b, l, h, d), jnp.float32)
    v2 = jnp.asarray(rng.randn(b, l, h, d), jnp.float32)

    # Two chained steps with global offsets, as the ring executes them: q shard at
    # offset l attends its own kv (offset l) then the previous shard's (offset 0).
    bw = blockwise_attention_with_carry(q, k1, v1, None, causal=True,
                                        block_size=16, q_offset=l, k_offset=l)
    bw = blockwise_attention_with_carry(q, k2, v2, bw, causal=True,
                                        block_size=16, q_offset=l, k_offset=0)
    fl = flash_attention_with_carry(q, k1, v1, None, causal=True,
                                    q_offset=l, k_offset=l,
                                    q_block=16, k_block=16)
    fl = flash_attention_with_carry(q, k2, v2, fl, causal=True,
                                    q_offset=l, k_offset=0,
                                    q_block=16, k_block=16)
    for a, b_, name in zip(fl, bw, ("acc", "m", "l")):
        _close(a, b_, atol=1e-5, rtol=1e-5, mxu=0.05, err_msg=name)


@pytest.mark.parametrize("q_offset,k_offset,blocks", [
    (256, 0, 128),      # the shard lies wholly before the queries: plain tiles only
    (0, 256, 128),      # wholly after: every tile skipped, the carry passes through
    (256, 256, 128),    # on the diagonal: plain, masked and skipped tiles
    (192, 64, 64),      # crossed, the offsets off the block grid
    (128, 0, 256),      # one 256-key block a step, the diagonal never reached
], ids=["visible", "masked", "diagonal", "crossed-unaligned", "one-block"])
def test_flash_carry_block_classes_match_blockwise(q_offset, k_offset, blocks):
    """The ring step under traced offsets: the tile classes are decided at run
    time from the SMEM scalars. Two chained steps (an own-shard step first, so
    the carry is not at its NEG_INF start) against the pure-JAX carry."""
    from autodist_tpu.ops.blockwise_attention import blockwise_attention_with_carry
    from autodist_tpu.ops.flash_attention import flash_attention_with_carry

    rng = np.random.RandomState(11)
    length = 256
    q, k1, v1, k2, v2 = (jnp.asarray(rng.randn(1, length, 2, 16), jnp.float32)
                         for _ in range(5))

    @jax.jit
    def flash(q_off, k_off):
        carry = flash_attention_with_carry(
            q, k1, v1, None, causal=True, q_offset=q_off, k_offset=q_off,
            q_block=blocks, k_block=blocks)
        return flash_attention_with_carry(
            q, k2, v2, carry, causal=True, q_offset=q_off, k_offset=k_off,
            q_block=blocks, k_block=blocks)

    want = blockwise_attention_with_carry(
        q, k1, v1, None, causal=True, block_size=64, q_offset=q_offset,
        k_offset=q_offset)
    want = blockwise_attention_with_carry(
        q, k2, v2, want, causal=True, block_size=64, q_offset=q_offset,
        k_offset=k_offset)
    got = flash(jnp.int32(q_offset), jnp.int32(k_offset))
    for a, b_, name in zip(got, want, ("acc", "m", "l")):
        _close(a, b_, atol=2e-5, rtol=1e-5, mxu=0.05, err_msg=name)


@_NEEDS_MESH
@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_matches_ring_blockwise(causal):
    """Forward AND gradients of the pallas-backed ring equal the pure-JAX ring."""
    from functools import partial

    mesh = build_mesh(axes={"seq": 4, "data": 2})
    rng = np.random.RandomState(1)
    b, l, h, d = 2, 64, 2, 8
    q = jnp.asarray(rng.randn(b, l, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, l, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, l, h, d), jnp.float32)

    def run(impl):
        spec = P(("data", "reduce"), "seq", None, None)
        fn = jax.shard_map(
            lambda q_, k_, v_: ring_attention(q_, k_, v_, causal=causal,
                                              block_size=16, impl=impl),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)

        def loss(q_, k_, v_):
            return jnp.sum(fn(q_, k_, v_) ** 2)

        with mesh:
            val, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        return val, grads

    val_bw, g_bw = run("blockwise")
    val_fl, g_fl = run("flash")
    _close(float(val_fl), float(val_bw), atol=0, rtol=1e-5)
    for a, b_, name in zip(g_fl, g_bw, "qkv"):
        _close(a, b_, atol=1e-4, rtol=1e-4, mxu=0.05, err_msg=f"d{name}")
