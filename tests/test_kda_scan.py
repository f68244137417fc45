"""Kimi Delta Attention's recurrence (``ops/kda_scan.py``): the chunked
operator under both of its forms, ``impl="xla"`` and the two Pallas kernels
(interpret mode on the CPU), against the recurrence itself a token at a time
(``benchmark/reference/bailing_hybrid.py`` ``delta_rule``) — forward, all five
gradients (q, k, v, g, beta) and the state after the last token, at one, two
and three chunks and a length that is no multiple of the chunk, with gates at
both ends of (-5, 0); ``beta = 0`` is a decayed state that nothing writes;
``g = 0`` and ``beta = 1`` the ungated delta rule."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.ops import kda_scan as ks
from benchmark.reference import bailing_hybrid as reference

CHUNK, HEADS, DEPTH = 64, 2, 128
NAMES = ("q", "k", "v", "g", "beta")


def _operands(length, dtype=jnp.float32, heads=HEADS, depth=DEPTH, batch=1,
              seed=0):
    """Normalised q and k, a log-decay that reaches both ends of (-5, 0) in
    every chunk, beta over (0, 1), and the weights of the scalar whose
    gradients are compared."""
    keys = jax.random.split(jax.random.PRNGKey(seed + length), 6)
    shape = (batch, length, heads, depth)
    q, k, v, w = (jax.random.normal(key, shape) for key in keys[:4])
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    g = -5.0 * jax.nn.sigmoid(6.0 * jax.random.normal(keys[4], shape))
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(keys[5], shape[:3]))
    return ((unit(q) * depth ** -0.5).astype(dtype), unit(k).astype(dtype),
            v.astype(dtype), g, beta), w


def _value_and_grads(scan, inputs, w):
    def loss(*inputs):
        out = scan(*inputs).astype(jnp.float32)
        return jnp.sum(out * w), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(5)), has_aux=True))(*inputs)
    return out, grads


def _recurrence(*inputs):
    with jax.default_matmul_precision("highest"):
        return reference.delta_rule(*(x.astype(jnp.float32) for x in inputs))


def _close(got, want, tol, what=""):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.max(np.abs(got - want)) <= tol * max(np.max(np.abs(want)), 1e-30), what


@pytest.mark.parametrize("length", [CHUNK, 2 * CHUNK, 3 * CHUNK, 2 * CHUNK + 37],
                         ids=["1-chunk", "2-chunks", "3-chunks", "ragged"])
@pytest.mark.parametrize("impl", ks.IMPLS)
def test_forward_five_gradients_and_last_state_match_the_recurrence(impl, length):
    inputs, w = _operands(length)
    assert float(inputs[3].min()) < -4.9 and float(inputs[3].max()) > -0.1
    out, grads = _value_and_grads(
        lambda *x: ks.kda_scan(*x, chunk=CHUNK, impl=impl), inputs, w)
    want, want_grads = _value_and_grads(lambda *x: _recurrence(*x)[0], inputs, w)
    _close(out, want, 1e-5, "o")
    for name, got, ref in zip(NAMES, grads, want_grads):
        assert np.any(ref), name
        _close(got, ref, 1e-5, f"d{name}")
    _close(ks.kda_last_state(*inputs, chunk=CHUNK, impl=impl),
           _recurrence(*inputs)[1], 1e-5, "state")
    chunks = -(-length // CHUNK)
    assert telemetry.gauge("kda.chunks").value == chunks
    assert telemetry.gauge("kda.state_kept_bytes").value == \
        chunks * HEADS * DEPTH * DEPTH * 4


@pytest.mark.parametrize("impl", ks.IMPLS)
def test_bfloat16_operands_stay_inside_their_band(impl):
    """The products take bfloat16 operands (8 bits of mantissa) and accumulate
    in float32; the decay, the running sums, the solve and the state stay
    float32: within 2% of the float32 recurrence as whole arrays."""
    inputs, w = _operands(2 * CHUNK, jnp.bfloat16)
    out, grads = _value_and_grads(
        lambda *x: ks.kda_scan(*x, chunk=CHUNK, impl=impl), inputs, w)
    assert out.dtype == jnp.float32 and grads[0].dtype == jnp.bfloat16
    assert grads[3].dtype == grads[4].dtype == jnp.float32
    want, want_grads = _value_and_grads(lambda *x: _recurrence(*x)[0], inputs, w)
    for got, ref in zip((out, *grads), (want, *want_grads)):
        got, ref = (np.asarray(x, np.float32) for x in (got, ref))
        assert np.linalg.norm(got - ref) <= 2e-2 * np.linalg.norm(ref)


@pytest.mark.parametrize("impl", ks.IMPLS)
def test_the_rows_form_is_the_four_dimensional_call(impl):
    inputs, _ = _operands(CHUNK + 16)
    q, k, v, g, beta = inputs
    rows = lambda x: x.reshape(*x.shape[:2], -1)  # noqa: E731
    want = ks.kda_scan(*inputs, chunk=CHUNK, impl=impl)
    got = ks.kda_scan(rows(q), rows(k), rows(v), rows(g), beta, chunk=CHUNK,
                      impl=impl)
    assert got.shape == rows(q).shape
    np.testing.assert_array_equal(got.reshape(want.shape), want)


@pytest.mark.parametrize("impl", ks.IMPLS)
def test_beta_zero_is_a_decayed_state_that_nothing_writes(impl):
    (q, k, v, g, beta), _ = _operands(2 * CHUNK)
    out = ks.kda_scan(q, k, v, g, jnp.zeros_like(beta), chunk=CHUNK, impl=impl)
    assert not np.any(out)
    assert not np.any(ks.kda_last_state(q, k, v, g, jnp.zeros_like(beta),
                                        chunk=CHUNK, impl=impl))


@pytest.mark.parametrize("impl", ks.IMPLS)
def test_no_decay_and_beta_one_is_the_ungated_delta_rule(impl):
    """``S_t = (I - k_t k_t^T) S_{t-1} + k_t v_t^T``: after writing ``v_t`` at
    a unit key the state answers that key with ``v_t`` exactly."""
    (q, k, v, g, beta), _ = _operands(CHUNK + 8)
    out = ks.kda_scan(k, k, v, jnp.zeros_like(g), jnp.ones_like(beta),
                      chunk=CHUNK, impl=impl)
    _close(out, v, 1e-5)

    def ungated(q, k, v):       # [L, D] of one head
        def step(state, x):
            q_t, k_t, v_t = x
            state = state - jnp.outer(k_t, k_t @ state) + jnp.outer(k_t, v_t)
            return state, state.T @ q_t
        return jax.lax.scan(step, jnp.zeros((DEPTH, DEPTH)), (q, k, v))[1]

    out = ks.kda_scan(q, k, v, jnp.zeros_like(g), jnp.ones_like(beta),
                      chunk=CHUNK, impl=impl)
    with jax.default_matmul_precision("highest"):
        want = ungated(q[0, :, 1], k[0, :, 1], v[0, :, 1])
    _close(out[0, :, 1], want, 1e-5)


def test_a_wrong_call_is_refused_by_name():
    (q, k, v, g, beta), _ = _operands(CHUNK)
    with pytest.raises(ValueError, match="Unknown kda impl"):
        ks.kda_scan(q, k, v, g, beta, impl="mosaic")
    with pytest.raises(ValueError, match="want \\[B, L, H\\]"):
        ks.kda_scan(q, k, v, g, beta[:, :-1])
    with pytest.raises(ValueError, match="whole sub-blocks"):
        ks.kda_scan(q, k, v, g, beta, chunk=24)
    with pytest.raises(ValueError, match="multiple of 128"):
        ks.kda_scan(q[..., :64], k[..., :64], v[..., :64], g[..., :64], beta,
                    impl="pallas")
    # the plain form takes any head width and any chunk of whole sub-blocks
    out = ks.kda_scan(q[..., :32], k[..., :32], v[..., :32], g[..., :32], beta,
                      chunk=32)
    assert out.shape == (1, CHUNK, HEADS, 32)
