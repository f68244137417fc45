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

    gf = jax.grad(fused, argnums=(0, 1, 2))(h, w, b)
    gr = jax.grad(ref, argnums=(0, 1, 2))(h, w, b)
    for a, e in zip(gf, gr):
        np.testing.assert_allclose(a, e, rtol=2e-4, atol=2e-5)


def test_fit_blocks_shrinks_for_large_d_f32_table():
    """The default (bn=512, bv=1024) tiles fit d=512 but overflow VMEM at
    d=768 with an f32 table — the dw kernel double-buffers both the table
    tile and the dw output tile, plus an f32 accumulator. The fitter must
    shrink bv at d=768 (the compiler refuses the kernel on overflow) and
    leave the d=512 flagship tiling alone."""
    from autodist_tpu.ops.fused_xent import (_VMEM_BUDGET, _fit_blocks,
                                             _vmem_need)

    # bf16 h (2 bytes), f32 table (4 bytes) — the model zoo's param_dtype.
    assert _fit_blocks(512, 512, 1024, 2, 4, backward=True) == (512, 1024)
    bn, bv = _fit_blocks(768, 512, 1024, 2, 4, backward=True)
    assert bv < 1024
    assert _vmem_need("dw", 768, bn, bv, 2, 4) <= _VMEM_BUDGET
    # d=1024 shrinks further but never below one lane tile.
    bn2, bv2 = _fit_blocks(1024, 512, 1024, 2, 4, backward=True)
    assert 128 <= bv2 <= bv
    # The backward budget covers BOTH its kernels: the dh footprint at large d
    # with f32 activations must also bound the result.
    bn3, bv3 = _fit_blocks(2048, 512, 1024, 4, 4, backward=True)
    assert _vmem_need("dh", 2048, bn3, bv3, 4, 4) <= _VMEM_BUDGET
    # Odd lane multiples clamp at one lane tile, never below (192 -> 128,
    # not 96).
    bn4, bv4 = _fit_blocks(2048, 512, 192, 4, 4, backward=True)
    assert bv4 == 128 and bn4 >= 128
    # A dim no tiling can fit refuses with an actionable error instead of
    # leaving the caller with the compiler's allocation-size message.
    with pytest.raises(ValueError, match="VMEM"):
        _fit_blocks(32768, 512, 1024, 4, 4, backward=True)


def test_fit_blocks_counts_in_kernel_temporaries_for_bf16_table():
    """A 2-byte table halves the table tiles but not what the kernel body
    spills to VMEM: at d=1024 the default tiles' buffers alone come to
    14.5 MiB, the compiler counted 18.4 MiB (dv) and 16.7 MiB (vd) against
    its 16 MiB limit, and the fitter has to shrink them
    (tests/test_chip_compile.py compiles the result)."""
    from autodist_tpu.ops.fused_xent import (_VMEM_BUDGET, _fit_blocks,
                                             _vmem_need)

    assert _vmem_need("dw", 1024, 512, 1024, 2, 2) > 16 << 20
    bn, bv = _fit_blocks(1024, 512, 1024, 2, 2, backward=True)
    assert (bn, bv) == (512, 512)
    assert max(_vmem_need(k, 1024, bn, bv, 2, 2)
               for k in ("dh", "dw")) <= _VMEM_BUDGET
    # The forward kernel alone keeps the default tiles.
    assert _fit_blocks(1024, 512, 1024, 2, 2, backward=False) == (512, 1024)


def test_shrunken_blocks_stay_value_exact(monkeypatch):
    """Force the fitter to shrink at small shapes (tiny budget) and check the
    kernel still matches the XLA reference — block size must only change
    tiling, never values."""
    from autodist_tpu.ops import fused_xent as fx

    # 384 KiB: big enough for the minimum tiling (whose accounted footprint
    # now includes the dw kernel's db_acc scratch + db output tile), small
    # enough that the requested (64, 256) blocks must shrink to (64, 128).
    monkeypatch.setattr(fx, "_VMEM_BUDGET", 384 << 10)
    h, w, b = _data(128, 64, 320, jnp.float32, seed=6)
    got = fx.matmul_logsumexp(h, w, b, 64, 256)
    np.testing.assert_allclose(got, _ref_lse(h, w, b), **_f32_tol())
    gf = jax.grad(lambda h, w, b: jnp.sum(
        fx.matmul_logsumexp(h, w, b, 64, 256) * 0.01), argnums=(0, 1, 2))(h, w, b)
    gr = jax.grad(lambda h, w, b: jnp.sum(
        _ref_lse(h, w, b) * 0.01), argnums=(0, 1, 2))(h, w, b)
    for a, e in zip(gf, gr):
        np.testing.assert_allclose(a, e, **_f32_tol(rtol=2e-4, atol=2e-5))


def test_grads_bf16_track_f32():
    h, w, b = _data(128, 64, 256, jnp.bfloat16, seed=4)

    def fused(h, w, b):
        return jnp.mean(matmul_logsumexp(h, w, b, 64, 128))

    gf = jax.grad(fused, argnums=(0, 1))(h, w, b)
    gr = jax.grad(
        lambda h, w, b: jnp.mean(_ref_lse(h, w, b)), argnums=(0, 1))(
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
    gf = jax.grad(lambda h, w: jnp.mean(fused_softmax_xent(h, w, targets, b,
                                                           64, 128)),
                  argnums=(0, 1))(h, w)
    gr = jax.grad(
        lambda h, w: jnp.mean(-jnp.take_along_axis(
            jax.nn.log_softmax(h @ w + b, axis=-1),
            targets[:, None], axis=-1)[:, 0]), argnums=(0, 1))(h, w)
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
    g_dv = jax.grad(f_dv, argnums=(0, 1, 2))(h, w, b)
    g_vd = jax.grad(f_vd, argnums=(0, 1, 2))(h, w_vd, b)
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
    grads = jax.grad(lambda h, w, b: jnp.mean(matmul_logsumexp(h, w, b, 128, 128)),
                     argnums=(0, 1, 2))(h, w, b)
    for g_ in grads:
        assert np.isfinite(np.asarray(g_)).all()
    gr = jax.grad(lambda h, w, b: jnp.mean(_ref_lse(h, w, b)),
                  argnums=(0, 1, 2))(h, w, b)
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
