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

    g_ref = jax.jit(jax.grad(f_ref, argnums=(0, 1, 2)))(q, k, v)
    g_blk = jax.jit(jax.grad(f_blk, argnums=(0, 1, 2)))(q, k, v)
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

    grads = jax.jit(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)

    def f_ref(q, k, v):
        return jnp.sum(_reference(q, k, v) ** 2)

    want = jax.jit(jax.grad(f_ref, argnums=(0, 1, 2)))(q, k, v)
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
    got = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(loss(plain), argnums=(0, 1, 2)))(q, k, v)
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


def _backward_both_paths(monkeypatch, q, k, v, causal, q_block, k_block,
                          q_offset=0, k_offset=0, out_dtype=None):
    """``_flash_backward`` on a forward's residuals, in one pass and with the
    byte limit at 0 (the split path), and ``flash.bwd.passes`` of each.
    Offsets other than 0 enter traced, as the ring's do."""
    import importlib
    from autodist_tpu import telemetry
    fa = importlib.import_module("autodist_tpu.ops.flash_attention")

    g = jnp.asarray(np.random.RandomState(13).randn(*q.shape), q.dtype)
    interpret = fa._use_interpret()
    out, lse = fa._flash_forward(q, k, v, causal and q.shape[1] == k.shape[1],
                                 q_block, k_block, interpret)

    def backward(**offsets):
        return fa._flash_backward(q, k, v, out, lse, g, causal, q_block, k_block,
                                  interpret, out_dtype=out_dtype, **offsets)

    results, passes = [], []
    for limit in (fa._RESIDENT_DQ_BYTES, 0):
        monkeypatch.setattr(fa, "_RESIDENT_DQ_BYTES", limit)
        if q_offset or k_offset:
            results.append(jax.jit(
                lambda q_off, k_off: backward(q_offset=q_off, k_offset=k_off))(
                    jnp.int32(q_offset), jnp.int32(k_offset)))
        else:
            results.append(backward())
        passes.append(telemetry.gauge("flash.bwd.passes").value)
    return results, passes


def _randn(rng, *shape):
    return jnp.asarray(rng.randn(*shape), jnp.float32)


# name -> (Lq, Lk, D, causal, q_block, k_block, out_dtype)
_ONE_PASS_CASES = {
    **{name: (length, length, 16, causal, q_block, k_block, None)
       for name, (length, causal, q_block, k_block) in _BLOCK_CLASS_CASES.items()},
    "head-dim-128": (128, 128, 128, True, 64, 64, None),    # the scale is not a power of two
    "ragged-lq-ne-lk": (100, 150, 16, False, 32, 64, None),
    "float32-out": (128, 128, 16, True, 32, 32, jnp.float32),
}


@pytest.mark.parametrize("case", list(_ONE_PASS_CASES))
def test_flash_backward_one_pass_matches_split(case, monkeypatch):
    """dQ, dK and dV from one recomputed score tile against the two-kernel
    schedule of the same block body: equal within float32 accumulation order."""
    lq, lk, d, causal, q_block, k_block, out_dtype = _ONE_PASS_CASES[case]
    rng = np.random.RandomState(17)
    q, k, v = _randn(rng, 1, lq, 2, d), _randn(rng, 1, lk, 2, d), _randn(rng, 1, lk, 2, d)
    (one, split), passes = _backward_both_paths(
        monkeypatch, q, k, v, causal, q_block, k_block, out_dtype=out_dtype)
    assert passes == [1, 2]
    for a, b, name in zip(one, split, "qkv"):
        assert a.dtype == b.dtype == (out_dtype or jnp.float32)
        _close(a, b, atol=2e-5, rtol=1e-5, mxu=0.05, err_msg=f"d{name}")


@pytest.mark.parametrize("q_offset,k_offset", [(128, 128), (128, 0), (0, 128)],
                         ids=["diagonal", "visible", "hidden"])
def test_flash_backward_one_pass_matches_split_under_traced_offsets(
        q_offset, k_offset, monkeypatch):
    """The ring's backward step: traced offsets, float32 outputs, the classes
    decided at run time. A shard wholly after the queries gives zeros."""
    rng = np.random.RandomState(19)
    q, k, v = (_randn(rng, 1, 128, 2, 16) for _ in range(3))
    (one, split), passes = _backward_both_paths(
        monkeypatch, q, k, v, True, 32, 32, q_offset=q_offset, k_offset=k_offset,
        out_dtype=jnp.float32)
    assert passes == [1, 2]
    for a, b, name in zip(one, split, "qkv"):
        assert a.dtype == jnp.float32
        _close(a, b, atol=2e-5, rtol=1e-5, mxu=0.05, err_msg=f"d{name}")
        assert bool(jnp.any(a != 0)) == (k_offset <= q_offset)


@pytest.mark.parametrize("length,causal,want", [
    (1024, True, (1, 2, 1)),    # 512 x 512 tiles: two on the diagonal, one below, one above
    (300, True, (0, 1, 0)),     # ragged, one tile
    (2048, True, (6, 4, 6)),
], ids=["L1024-causal", "L300-ragged", "L2048-causal"])
def test_flash_backward_gauges_equal_hand_count(length, causal, want):
    """``flash.bwd.tiles_*`` (score tiles of one (batch, head) by class) and
    ``flash.bwd.passes``, set when the backward is traced."""
    from autodist_tpu import telemetry

    qkv = jax.ShapeDtypeStruct((1, length, 2, 64), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=causal).astype(jnp.float32).sum()

    jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)
    got = tuple(telemetry.gauge(f"flash.bwd.tiles_{name}").value
                for name in ("plain", "masked", "skipped"))
    assert got == want
    assert telemetry.gauge("flash.bwd.passes").value == 1


def test_flash_backward_byte_limit_switches_the_path(monkeypatch):
    """Past ``_RESIDENT_DQ_BYTES`` of float32 dQ a (batch, head) the backward
    is the two-kernel schedule: the gradients of ``flash_attention`` are the
    same on either side of the limit."""
    import importlib
    from autodist_tpu import telemetry
    fa = importlib.import_module("autodist_tpu.ops.flash_attention")

    rng = np.random.RandomState(23)
    q, k, v = (_randn(rng, 1, 96, 2, 16) for _ in range(3))

    def grads():
        return jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, q_block=32, k_block=32) ** 2), argnums=(0, 1, 2)))(q, k, v)

    resident = 96 * 16 * 4          # dQ of one (batch, head), float32
    got = []
    for limit, passes in ((resident, 1), (resident - 1, 2)):
        monkeypatch.setattr(fa, "_RESIDENT_DQ_BYTES", limit)
        got.append(grads())
        assert telemetry.gauge("flash.bwd.passes").value == passes
    for a, b, name in zip(*got, "qkv"):
        _close(a, b, atol=2e-5, rtol=1e-5, mxu=0.05, err_msg=f"d{name}")


# name -> (L, heads, D, q_block, k_block, dQ byte limit, operands XLA relays
# around the forward / the backward) of a call whose q, k, v are handed as the
# projections' rows, [B, L, H * D]: an operand whose heads are whole lane
# tiles wide is read and written where it lies, through the index maps;
# head_dim 64 and a call that pads its rows to a block keep the transposes to
# [B * H, L, D] that every call had (4 / 7).
_IN_PLACE_CASES = {
    "128-wide": (256, 3, 128, 128, 128, None, (0, 0)),
    "128-wide-split": (256, 3, 128, 128, 128, 0, (0, 0)),
    "128-wide-blocks-from-the-shape": (1024, 2, 128, None, None, None, (0, 0)),
    "256-wide": (128, 2, 256, 64, 64, None, (0, 0)),
    "64-wide": (256, 3, 64, 128, 128, None, (4, 7)),
    "128-wide-ragged": (300, 2, 128, 128, 128, None, (4, 7)),
}


@pytest.mark.parametrize("case", list(_IN_PLACE_CASES))
def test_flash_operands_stay_where_the_projections_put_them(case, monkeypatch):
    """Forward and all gradients of a call on rows against plain float32
    attention, the count of relaid operands, and what the same call gives
    on [B, L, H, D] operands, every one transposed around the kernels (the
    layout of every call before PR 41): the tiles and their arithmetic are
    the same, so the result is, bit for bit; the gradients differ by the
    order of one float32 sum (the row term D, which the in-place path takes
    as a product with the heads' 0 / 1 columns)."""
    import importlib
    from autodist_tpu import telemetry
    fa = importlib.import_module("autodist_tpu.ops.flash_attention")

    length, heads, d, q_block, k_block, dq_bytes, relaid = _IN_PLACE_CASES[case]
    if dq_bytes is not None:
        monkeypatch.setattr(fa, "_RESIDENT_DQ_BYTES", dq_bytes)
    rng = np.random.RandomState(41)
    q, k, v, weights = (_randn(rng, 2, length, heads, d) for _ in range(4))
    rows = lambda x: x.reshape(2, length, heads * d)  # noqa: E731

    def run(attend, *operands):
        # one compiled program a call: the output and the three gradients of
        # one forward (the gauges are set when it is traced)
        def loss(*a):
            out = attend(*a)
            return jnp.sum(out * weights.reshape(out.shape)), out
        grads, out = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))(
            *operands)
        return (out,) + grads

    def flash(q, k, v):
        return flash_attention(q, k, v, q_block=q_block, k_block=k_block,
                               heads=(heads, heads))

    def gauges():
        return (telemetry.gauge("flash.fwd.operands_relaid").value,
                telemetry.gauge("flash.bwd.operands_relaid").value)

    got = run(flash, rows(q), rows(k), rows(v))
    assert gauges() == relaid
    assert all(x.shape == (2, length, heads * d) for x in got)
    got = [x.reshape(q.shape) for x in got]
    for a, b, name in zip(got, run(_reference, q, k, v), ("o", "dq", "dk", "dv")):
        _close(a, b, atol=3e-4, mxu=0.05, err_msg=name)
    relaid_all = run(flash, q, k, v)
    assert gauges() == (4, 7)
    np.testing.assert_array_equal(got[0], relaid_all[0], err_msg="o")
    for a, b, name in zip(got[1:], relaid_all[1:], ("dq", "dk", "dv")):
        _close(a, b, atol=2e-5, rtol=1e-5, mxu=0.05, err_msg=name)
    # one operand a call: v as rows (and with it the result), q and k not
    mixed = jax.jit(flash)(q, k, rows(v))
    assert mixed.shape == (2, length, heads * d)
    assert telemetry.gauge("flash.fwd.operands_relaid").value == \
        (2 if relaid == (0, 0) else 4)
    np.testing.assert_array_equal(mixed.reshape(q.shape), got[0])
    with pytest.raises(ValueError, match="heads"):
        flash_attention(rows(q), rows(k), rows(v))


def _transposes_around_the_kernels(lowered, least: int) -> int:
    """Of the operands and results of every ``tpu_custom_call`` in a lowered
    module that hold ``least`` elements or more, how many come straight from
    (or go straight into) a ``stablehlo.transpose``, reshapes and converts
    looked through."""
    def walk(op):
        yield op
        for region in op.regions:
            for block in region:
                for inner in block:
                    yield from walk(inner.operation)

    def big(value):
        return int(np.prod(value.type.shape)) >= least

    through = ("stablehlo.reshape", "stablehlo.convert")

    def source(value):
        owner = value.owner
        while getattr(owner, "name", None) in through:
            owner = owner.operands[0].owner
        return getattr(owner, "name", None)         # None: a block argument

    def sinks(value):
        for use in value.uses:
            user = use.owner
            if user.name in through:
                yield from sinks(user.results[0])
            else:
                yield user.name

    module = lowered.compiler_ir("stablehlo")
    calls = [op for op in walk(module.operation)
             if op.name == "stablehlo.custom_call"
             and "tpu_custom_call" in str(op.attributes["call_target_name"])]
    assert len(calls) == 2                      # flash_fwd, flash_bwd_dkv
    return sum(source(v) == "stablehlo.transpose"
               for call in calls for v in call.operands if big(v)) + sum(
        "stablehlo.transpose" in set(sinks(r))
        for call in calls for r in call.results if big(r))


# a cell's call -> (B, L, H, H_kv, D_qk, D_v, shared key columns, window,
# which of q, k, v its model hands as rows), and how many of the kernels' big
# operands XLA transposes around them
@pytest.mark.parametrize("shape,relaid", [
    ((8, 1024, 16, 16, 64, 64, 0, None, ""), 11),      # gpt2m-*: the parent's 4 + 7
    ((4, 4096, 16, 16, 128, 128, 0, None, "v"), 6),    # olmoe's shape, v rows: q, k | q, k, dQ, dK
    # trinity-pretrain-8k, sliding: a group's dK passes through its sum first
    ((1, 8192, 32, 4, 128, 128, 0, 2048, "v"), (5, 6)),
    ((1, 8192, 32, 2, 128, 128, 0, None, "qkv"), 0),   # nemotron-pretrain-8k
    ((4, 4096, 16, 16, 128, 128, 0, None, "qkv"), 0),  # olmoe's shape, all rows
    ((1, 16384, 32, 32, 192, 128, 64, None, "kv"), 3),  # kanana-pretrain-16k: q | q, dQ
], ids=["gpt2m", "olmoe-v-rows", "trinity", "nemotron", "olmoe-all-rows", "kanana"])
def test_no_transpose_around_the_kernels_of_operands_that_stay(shape, relaid,
                                                                monkeypatch):
    """Forward and backward lowered FOR THE TPU (no chip: Mosaic's lowering
    checks the block shapes) at the cells' own shapes, the operands handed
    as Trinity's, Nemotron's and Kanana's models hand them (and OLMoE's
    shape both ways; its model hands [B, L, H, D]): no ``stablehlo.transpose``
    feeds or follows a kernel on an operand handed as rows of whole lane
    tiles a head, and the count agrees with the ``operands_relaid`` gauges
    everywhere. Kanana's call takes its keys and values packed, as ``kv_up``
    writes them."""
    import importlib
    from autodist_tpu import telemetry
    fa = importlib.import_module("autodist_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_use_interpret", lambda: False)
    b, length, h, h_kv, d, d_v, d_s, window, as_rows = shape

    def struct(name, n, width):
        return jax.ShapeDtypeStruct(
            (b, length, n * width) if name in as_rows else (b, length, n, width),
            jnp.bfloat16)

    q = struct("q", h, d)
    if d_s:
        operands = (q, struct("kv", h_kv, d - d_s + d_v),
                    jax.ShapeDtypeStruct((b, length, d_s), jnp.bfloat16))
        attend = lambda q, kv, ks: flash_attention(  # noqa: E731
            q, kv, None, k_shared=ks, heads=(h, h_kv))
    else:
        operands = (q, struct("k", h_kv, d), struct("v", h_kv, d_v))
        attend = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, window=window, heads=(h, h_kv))
    lowered = jax.jit(jax.grad(
        lambda *a: attend(*a).astype(jnp.float32).sum(),
        argnums=tuple(range(len(operands))))).trace(*operands).lower(
            lowering_platforms=("tpu",))
    transposes, relaid = relaid if isinstance(relaid, tuple) else (relaid,) * 2
    assert _transposes_around_the_kernels(lowered, b * length * h_kv * 64) \
        == transposes
    assert (telemetry.gauge("flash.fwd.operands_relaid").value
            + telemetry.gauge("flash.bwd.operands_relaid").value) == relaid


def test_kernel_names_unchanged():
    from autodist_tpu.ops import named_call
    assert named_call.KERNEL_NAMES == (
        "flash_fwd", "flash_bwd_dkv", "flash_bwd_dq", "flash_carry",
        "xent_fwd", "xent_bwd_dh", "xent_bwd_dw",
        "moe_gmm_fwd", "moe_gmm_bwd_dx", "moe_gmm_bwd_dw",
        "short_conv_fwd", "short_conv_bwd",
        "moe_rows_gather", "moe_rows_combine",
        "ssd_fwd", "ssd_bwd", "conv_silu_fwd", "conv_silu_bwd",
        "selective_scan_fwd", "selective_scan_bwd",
        "flash_sink_fwd", "flash_sink_bwd_dkv", "flash_sink_bwd_dq",
        "gated_norm_fwd", "gated_norm_bwd", "eva_fwd", "eva_bwd",
        "kda_fwd", "kda_bwd")


@_NEEDS_MESH
@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_single_device(causal):
    """Sequence sharded over a 4-way seq axis: ring result == full attention."""
    mesh = build_mesh(axes={const.MESH_AXIS_SEQ: 4, const.MESH_AXIS_DATA: 2})
    q, k, v = _qkv(5)
    want = _reference(q, k, v, causal)

    spec = P(const.MESH_AXIS_DATA, const.MESH_AXIS_SEQ, None, None)
    # jitted: a bare shard_map runs primitive by primitive on the CPU mesh
    fn = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, causal=causal, block_size=16),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False))
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

    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    def loss_ref(q, k, v):
        return jnp.sum(_reference(q, k, v) ** 2)

    want = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
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
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)

    val_bw, g_bw = run("blockwise")
    val_fl, g_fl = run("flash")
    _close(float(val_fl), float(val_bw), atol=0, rtol=1e-5)
    for a, b_, name in zip(g_fl, g_bw, "qkv"):
        _close(a, b_, atol=1e-4, rtol=1e-4, mxu=0.05, err_msg=f"d{name}")
