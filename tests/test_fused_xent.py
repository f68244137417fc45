"""Fused matmul+logsumexp kernels vs the XLA reference, values and gradients.

Same testing pattern as the flash-attention kernels: interpret mode on the
CPU-sim backend runs the identical kernel code the chip runs compiled.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.ops.fused_xent import fused_softmax_xent, matmul_logsumexp


def _ref_lse(h, w, b):
    logits = h.astype(jnp.float32) @ w.astype(jnp.float32)
    if b is not None:
        logits = logits + b
    return jax.nn.logsumexp(logits, axis=-1)


def _f32_tol(rtol=1e-5, atol=1e-5):
    """f32 comparison tolerance: exact-ish on CPU (the given values); on
    TPU both the kernel and the XLA reference run f32 matmuls
    at MXU (bf16-pass) precision, so two correct implementations legitimately
    differ by ~1e-3. The backend test lives here exactly once."""
    if jax.default_backend() == "tpu":
        return dict(rtol=5e-3, atol=5e-3)
    return dict(rtol=rtol, atol=atol)


def _data(n, d, v, dtype, seed=0):
    rng = np.random.RandomState(seed)
    h = jnp.asarray(rng.randn(n, d), dtype) * 0.5
    w = jnp.asarray(rng.randn(d, v), dtype) * 0.1
    b = jnp.asarray(rng.randn(v), jnp.float32) * 0.1
    return h, w, b


@pytest.mark.parametrize("n,d,v", [(256, 128, 512), (200, 128, 384), (64, 64, 129)])
def test_lse_matches_reference(n, d, v):
    h, w, b = _data(n, d, v, jnp.float32)
    got = matmul_logsumexp(h, w, b, 128, 256)
    np.testing.assert_allclose(got, _ref_lse(h, w, b), rtol=1e-5, atol=1e-5)


def test_lse_no_bias():
    h, w, _ = _data(128, 64, 320, jnp.float32)
    got = matmul_logsumexp(h, w, None, 64, 128)
    np.testing.assert_allclose(got, _ref_lse(h, w, None), rtol=1e-5, atol=1e-5)


def test_grads_match_reference_f32():
    h, w, b = _data(192, 64, 300, jnp.float32, seed=3)

    def fused(h, w, b):
        return jnp.sum(matmul_logsumexp(h, w, b, 64, 128) * 0.01)

    def ref(h, w, b):
        return jnp.sum(_ref_lse(h, w, b) * 0.01)

    gf = jax.jit(jax.grad(fused, argnums=(0, 1, 2)))(h, w, b)
    gr = jax.jit(jax.grad(ref, argnums=(0, 1, 2)))(h, w, b)
    for a, e in zip(gf, gr):
        np.testing.assert_allclose(a, e, rtol=2e-4, atol=2e-5)


_RIDGE = 240.0   # FLOP / byte of a v5e: 197 TFLOP/s over 819 GB/s

# (rows, d, vocab, activation bytes, table bytes): the four cells' calls (the
# two GPT-2 cells make the same one), and the shapes the fitter was first
# written against.
_FIT_SHAPES = {
    "olmoe-cell": (16_384, 2048, 50_304, 2, 4),
    "gpt2-cell": (8_192, 1024, 50_257, 2, 4),
    "trinity-cell": (8_192, 2048, 25_024, 2, 4),
    "flagship-d512": (98_304, 512, 32_000, 2, 4),
    "d768-f32-table": (2_048, 768, 32_000, 2, 4),
    "d1024-bf16-table": (2_048, 1024, 32_000, 2, 2),
    "d2048-f32-rows": (2_048, 2048, 32_000, 4, 4),
    "lm1b-vocab": (1_920, 1024, 793_471, 2, 4),
    "ragged-few-rows": (1_000, 1024, 50_257, 2, 4),
}


def _intensity(kernel, bn, bv, h_size, w_size):
    """FLOP per byte streamed from HBM (module docstring of ops/fused_xent)."""
    # the one pass streams the w tile in and the dw tile in and out for
    # three products
    return {"fwd": 2 * bn / w_size, "dh": 4 * bn / w_size,
            "dw": 4 * bv / h_size, "bwd": 2 * bn / w_size}[kernel]


# the one-pass backward's tiles, or None where the two kernels run
_ONE_PASS = {
    "olmoe-cell": (1024, 512),
    "gpt2-cell": (1024, 512),
    "trinity-cell": (1024, 512),
    "flagship-d512": (1024, 512),
    "d768-f32-table": (1024, 512),
    "d1024-bf16-table": None,      # dw accumulates in the table's own array
    "d2048-f32-rows": (1024, 128),
    "lm1b-vocab": (1024, 512),
    "ragged-few-rows": (1024, 512),
}


@pytest.mark.parametrize("kernel", ["fwd", "dh", "dw", "bwd"])
@pytest.mark.parametrize("shape", list(_FIT_SHAPES))
def test_fit_blocks_rule(shape, kernel):
    """Each kernel its own tiles from the shape: whole lane tiles, no larger
    than the rows and the vocabulary there are, that fit Mosaic's default
    limit (then no more is asked for) or the raised budget; at least at the
    chip's ridge in FLOP a streamed byte, and at twice it wherever the next
    larger block of the streamed axis would fit the raised budget too. The
    one-pass backward ("bwd"): whether the shape gets it, and at twice the
    ridge wherever it does (dw's way through HBM has to hide)."""
    from autodist_tpu.ops.fused_xent import (_BWD_VMEM_BUDGET,
                                             _DEFAULT_VMEM_BUDGET, _VMEM_BUDGET,
                                             _fit_blocks, _vmem_need)

    n, d, v, h_size, w_size = _FIT_SHAPES[shape]
    if kernel == "bwd":
        blocks = _fit_blocks("bwd", n, d, v, h_size, w_size)
        assert blocks == _ONE_PASS[shape]
        if blocks is not None:
            bn, bv = blocks
            assert bn % 128 == 0 and bv % 128 == 0 and -(-v // bv) >= 3
            assert _vmem_need("bwd", d, bn, bv, h_size, w_size) <= _BWD_VMEM_BUDGET
            assert _intensity("bwd", bn, bv, h_size, w_size) >= 2 * _RIDGE
        return
    bn, bv = _fit_blocks(kernel, n, d, v, h_size, w_size)
    need = _vmem_need(kernel, d, bn, bv, h_size, w_size)
    assert bn % 128 == 0 and bv % 128 == 0
    assert bn < n + 128 and bv < v + 128
    assert need <= _VMEM_BUDGET
    assert _intensity(kernel, bn, bv, h_size, w_size) >= _RIDGE
    if (need > _DEFAULT_VMEM_BUDGET
            and _intensity(kernel, bn, bv, h_size, w_size) < 2 * _RIDGE):
        grown = (bn, 2 * bv) if kernel == "dw" else (2 * bn, bv)
        assert (grown[0] > n or grown[1] > v
                or _vmem_need(kernel, d, *grown, h_size, w_size) > _VMEM_BUDGET)
    # the kernels of one call no longer share tiles where their needs differ
    if shape in ("olmoe-cell", "trinity-cell"):
        assert need > _DEFAULT_VMEM_BUDGET
        assert (bn, bv) == {"fwd": (1024, 1024), "dh": (512, 512),
                            "dw": (1024, 512)}[kernel]
    if shape == "gpt2-cell":   # the tiles PR 27 ran there, under the default limit
        assert need <= _DEFAULT_VMEM_BUDGET
        assert (bn, bv) == ((512, 1024) if kernel == "fwd" else (512, 512))
    if shape == "ragged-few-rows":
        assert bn == 512   # 1,000 rows, two blocks: the last one ragged


@pytest.mark.parametrize("kernel", ["fwd", "dh", "dw"])
def test_fit_blocks_starts_from_given_blocks_and_refuses_what_nothing_fits(kernel):
    from autodist_tpu.ops.fused_xent import _fit_blocks

    # the caller's blocks are where the fit starts: kept where they fit,
    assert _fit_blocks(kernel, 4096, 512, 32_000, 2, 4, 256, 384) == (256, 384)
    # halved (never below one lane tile: 192 -> 128, not 96) where they do not,
    bn, bv = _fit_blocks(kernel, 4096, 8192, 32_000, 4, 4, 512, 192)
    assert bv == 128 and bn >= 128
    # fewer rows than a block: one block of whole lane tiles,
    assert _fit_blocks(kernel, 200, 512, 32_000, 2, 4)[0] == 256
    # and a dim no tiling fits is refused by name, not by the compiler's
    # allocation size.
    with pytest.raises(ValueError, match="VMEM"):
        _fit_blocks(kernel, 4096, 65_536, 32_000, 4, 4)


def test_kernels_on_different_blocks_stay_value_exact(monkeypatch):
    """dh and dw on different (bn, bv), neither the forward's: a ragged last
    row block and vocab block in each, and a large bias entry against the
    padding rows. Values and gradients against the f32 reference."""
    from autodist_tpu.ops import fused_xent as fx

    tiles = {"fwd": (128, 256), "dh": (256, 128), "dw": (128, 384), "bwd": None}
    monkeypatch.setattr(fx, "_fit_blocks", lambda kernel, *a, **kw: tiles[kernel])
    h, w, b = _data(300, 64, 500, jnp.float32, seed=12)
    b = b.at[7].set(95.0)
    np.testing.assert_allclose(fx.matmul_logsumexp(h, w, b), _ref_lse(h, w, b),
                               **_f32_tol())
    gf = jax.jit(jax.grad(lambda h, w, b: jnp.sum(fx.matmul_logsumexp(h, w, b) * 0.01),
                          argnums=(0, 1, 2)))(h, w, b)
    gr = jax.jit(jax.grad(lambda h, w, b: jnp.sum(_ref_lse(h, w, b) * 0.01),
                          argnums=(0, 1, 2)))(h, w, b)
    for a, e in zip(gf, gr):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, e, **_f32_tol(rtol=2e-4, atol=2e-5))
    # the stored [V, D] layout through the same three tilings
    g_vd = jax.jit(jax.grad(lambda h, w, b: jnp.sum(fx.matmul_logsumexp(
        h, w, b, w_layout="vd") * 0.01), argnums=(0, 1, 2)))(h, w.T, b)
    np.testing.assert_allclose(g_vd[1], gr[1].T, **_f32_tol(rtol=2e-4, atol=2e-5))


@pytest.mark.parametrize("table_dtype,expected", [
    (jnp.float32, {"fwd.block_rows": 1024, "fwd.block_cols": 1024,
                   "bwd.passes": 1, "bwd.block_rows": 1024,
                   "bwd.block_cols": 512, "table_passes": 16 + 16}),
    (jnp.bfloat16, {"fwd.block_rows": 1024, "fwd.block_cols": 1024,
                    "bwd.passes": 2, "bwd.dh.block_rows": 1024,
                    "bwd.dh.block_cols": 512, "bwd.dw.block_rows": 1024,
                    "bwd.dw.block_cols": 512, "table_passes": 16 + 16}),
], ids=["one-pass", "two-kernels"])
def test_tracing_the_head_sets_its_block_gauges(table_dtype, expected):
    """``xent.*``: which backward runs (1 = one pass, 2 = dh and dw), its
    tiles (the one pass's, or each of the two kernels' own), the forward's,
    and the table's passes a call (row blocks of forward + backward), set
    when the op is traced; nothing executes."""
    from autodist_tpu import telemetry
    from autodist_tpu.ops.fused_xent import fused_softmax_xent

    struct = jax.ShapeDtypeStruct
    jax.eval_shape(
        jax.grad(lambda h, w, t: fused_softmax_xent(h, w, t).mean(),
                 argnums=(0, 1)),
        struct((16_384, 2048), jnp.bfloat16), struct((2048, 50_304), table_dtype),
        struct((16_384,), jnp.int32))        # olmoe-pretrain-4k's call, and
    # the same under a bfloat16 table, which dw cannot accumulate in
    got = {name: telemetry.gauge(f"xent.{name}").value for name in expected}
    assert got == expected


def test_shrunken_blocks_stay_value_exact(monkeypatch):
    """Force the fitter to shrink at small shapes (tiny budget) and check the
    kernel still matches the XLA reference — block size must only change
    tiling, never values."""
    from autodist_tpu.ops import fused_xent as fx

    # 384 KiB: big enough for the minimum tiling (whose accounted footprint
    # includes the dw kernel's db_acc scratch + db output tile), small
    # enough that the requested (64, 256) blocks must shrink to (64, 128).
    monkeypatch.setattr(fx, "_VMEM_BUDGET", 384 << 10)
    monkeypatch.setattr(fx, "_DEFAULT_VMEM_BUDGET", 384 << 10)
    assert fx._fit_blocks("dw", 128, 64, 320, 4, 4, 64, 256) == (64, 128)
    h, w, b = _data(128, 64, 320, jnp.float32, seed=6)
    got = fx.matmul_logsumexp(h, w, b, 64, 256)
    np.testing.assert_allclose(got, _ref_lse(h, w, b), **_f32_tol())
    gf = jax.jit(jax.grad(lambda h, w, b: jnp.sum(
        fx.matmul_logsumexp(h, w, b, 64, 256) * 0.01), argnums=(0, 1, 2)))(h, w, b)
    gr = jax.jit(jax.grad(lambda h, w, b: jnp.sum(
        _ref_lse(h, w, b) * 0.01), argnums=(0, 1, 2)))(h, w, b)
    for a, e in zip(gf, gr):
        np.testing.assert_allclose(a, e, **_f32_tol(rtol=2e-4, atol=2e-5))


def test_grads_bf16_track_f32():
    h, w, b = _data(128, 64, 256, jnp.bfloat16, seed=4)

    def fused(h, w, b):
        return jnp.mean(matmul_logsumexp(h, w, b, 64, 128))

    gf = jax.jit(jax.grad(fused, argnums=(0, 1)))(h, w, b)
    gr = jax.jit(jax.grad(
        lambda h, w, b: jnp.mean(_ref_lse(h, w, b)), argnums=(0, 1)))(
            h.astype(jnp.float32), w.astype(jnp.float32), b)
    for a, e in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32), e,
                                   rtol=0.05, atol=0.02)


def test_fused_xent_matches_composed_loss():
    n, d, v = 160, 64, 257
    h, w, b = _data(n, d, v, jnp.float32, seed=5)
    rng = np.random.RandomState(6)
    targets = jnp.asarray(rng.randint(0, v, (n,)), jnp.int32)

    nll = fused_softmax_xent(h, w, targets, b, 64, 128)
    logits = h @ w + b
    expected = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                    targets[:, None], axis=-1)[:, 0]
    np.testing.assert_allclose(nll, expected, **_f32_tol())

    # Full loss gradient (both the lse and the gathered true-logit paths).
    gf = jax.jit(jax.grad(lambda h, w: jnp.mean(fused_softmax_xent(h, w, targets, b,
                                                                   64, 128)),
                          argnums=(0, 1)))(h, w)
    gr = jax.jit(jax.grad(
        lambda h, w: jnp.mean(-jnp.take_along_axis(
            jax.nn.log_softmax(h @ w + b, axis=-1),
            targets[:, None], axis=-1)[:, 0]), argnums=(0, 1)))(h, w)
    tol = _f32_tol(rtol=2e-4, atol=2e-5)
    for a, e in zip(gf, gr):
        np.testing.assert_allclose(a, e, **tol)


def test_vd_layout_matches_dv():
    """[V, D]-stored tables (reference softmax_w layout) give identical values
    and gradients without the caller transposing."""
    h, w, b = _data(192, 64, 300, jnp.float32, seed=8)
    w_vd = w.T  # stored [V, D]

    def f_dv(h, w, b):
        return jnp.sum(matmul_logsumexp(h, w, b, 64, 128) * 0.01)

    def f_vd(h, w_vd, b):
        return jnp.sum(matmul_logsumexp(h, w_vd, b, 64, 128, None, "vd") * 0.01)

    np.testing.assert_allclose(f_vd(h, w_vd, b), f_dv(h, w, b), rtol=1e-6)
    g_dv = jax.jit(jax.grad(f_dv, argnums=(0, 1, 2)))(h, w, b)
    g_vd = jax.jit(jax.grad(f_vd, argnums=(0, 1, 2)))(h, w_vd, b)
    np.testing.assert_allclose(g_vd[0], g_dv[0], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(g_vd[1], g_dv[1].T, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(g_vd[2], g_dv[2], rtol=2e-4, atol=2e-5)
    # Mixed dtype: f32 table with bf16 activations, cast per-tile in the kernel.
    got = matmul_logsumexp(h.astype(jnp.bfloat16), w_vd, b, 64, 128, None, "vd")
    np.testing.assert_allclose(got, _ref_lse(h, w, b), rtol=0.02, atol=0.02)


def test_large_bias_with_padding_rows_stays_finite():
    """Regression: pad rows' lse must pad large-positive, or a bias entry > ~88
    overflows exp in the pad rows and NaNs the whole dw/db."""
    h, w, b = _data(100, 64, 256, jnp.float32, seed=9)   # 28 pad rows at bn=128
    b = b.at[5].set(95.0)
    grads = jax.jit(jax.grad(
        lambda h, w, b: jnp.mean(matmul_logsumexp(h, w, b, 128, 128)),
        argnums=(0, 1, 2)))(h, w, b)
    for g_ in grads:
        assert np.isfinite(np.asarray(g_)).all()
    gr = jax.jit(jax.grad(lambda h, w, b: jnp.mean(_ref_lse(h, w, b)),
                          argnums=(0, 1, 2)))(h, w, b)
    for a, e in zip(grads, gr):
        np.testing.assert_allclose(a, e, rtol=2e-4, atol=2e-5)


def test_fused_xent_vd_layout_matches():
    n, d, v = 96, 64, 200
    h, w, b = _data(n, d, v, jnp.float32, seed=10)
    rng = np.random.RandomState(11)
    targets = jnp.asarray(rng.randint(0, v, (n,)), jnp.int32)
    a = fused_softmax_xent(h, w, targets, b, 64, 128)
    bb = fused_softmax_xent(h, w.T, targets, b, 64, 128, w_layout="vd")
    np.testing.assert_allclose(bb, a, rtol=1e-5, atol=1e-5)


def test_jit_and_value_under_jit():
    h, w, b = _data(128, 64, 256, jnp.float32, seed=7)
    f = jax.jit(lambda h, w, b: matmul_logsumexp(h, w, b, 64, 128))
    np.testing.assert_allclose(f(h, w, b), _ref_lse(h, w, b), rtol=1e-5, atol=1e-5)


def _grads(h, w, b, coef, layout, n_block, v_block):
    return jax.jit(jax.grad(lambda h, w, b: jnp.sum(matmul_logsumexp(
        h, w, b, n_block, v_block, None, layout) * coef), argnums=(0, 1, 2)))(h, w, b)


@pytest.mark.parametrize("rows", ["bf16-ragged", "f32-whole"])
@pytest.mark.parametrize("n_v", [1, 2, 3, 5])
@pytest.mark.parametrize("n_n", [1, 2, 3])
@pytest.mark.parametrize("layout", ["dv", "vd"])
def test_one_pass_backward_matches_the_two_kernels(monkeypatch, layout, n_n, n_v,
                                                   rows):
    """dh, dw, db of the one pass against the two kernels, value for value:
    both table layouts, bias on with an entry past exp's range against the
    padding rows, one to three row blocks (dw summed across them through the
    aliased buffer) and one to five vocab blocks, the last of each ragged or
    whole. Below three vocab blocks the rule itself keeps the two kernels
    (``xent.bwd.passes``): there a tile would be read back before the
    pipeline had written it."""
    from autodist_tpu import telemetry
    from autodist_tpu.ops import fused_xent as fx

    ragged = rows == "bf16-ragged"
    n, v = n_n * 64 - 14 * ragged, n_v * 128 - 33 * ragged
    h, w, b = _data(n, 64, v, jnp.float32, seed=13)
    h = h.astype(jnp.bfloat16 if ragged else jnp.float32)
    b = b.at[5].set(95.0)
    coef = jnp.asarray(np.random.RandomState(14).randn(n), jnp.float32)
    w = w.T if layout == "vd" else w

    got = _grads(h, w, b, coef, layout, 64, 128)
    assert telemetry.gauge("xent.bwd.passes").value == (1 if n_v >= 3 else 2)
    rule = fx._fit_blocks
    monkeypatch.setattr(fx, "_fit_blocks", lambda kernel, *a, **kw: (
        None if kernel == "bwd" else rule(kernel, *a, **kw)))
    want = _grads(h, w, b, coef, layout, 64, 128)
    assert telemetry.gauge("xent.bwd.passes").value == 2
    for a, e in zip(got, want):
        assert a.dtype == e.dtype and np.isfinite(np.asarray(a, np.float32)).all()
        # the same float32 tile products summed in the same order; dh's
        # bfloat16 rounding may fall the other way on a last-bit difference
        tol = dict(rtol=1e-2, atol=1e-6) if a.dtype == jnp.bfloat16 else \
            _f32_tol(rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(e, np.float32), **tol)
