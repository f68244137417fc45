"""The Mamba-1 selective scan (``ops/selective_scan.py``): the plain path
against the literal token-by-token loop written out here, the two Pallas
kernels (interpret mode on the CPU) against the plain path, value and the
gradient of every one of the six inputs; a chunk boundary inside the sequence
and a length that is no whole number of chunks; nothing crosses from one
sequence of a batch to the next; no ``[L, E, N]`` array anywhere in the
forward or the backward; the wide operands reach the kernels as the ``[B, L,
E]`` rows they are; the gauges say what a call moves; under a mesh the kernels
run per device."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu import telemetry
from autodist_tpu.ops import selective_scan as ss
from autodist_tpu.ops.selective_scan import selective_scan

NAMES = ("x", "dt", "A", "B", "C", "D")


def token_loop(x, dt, A, B, C, D):
    """``s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T``, ``y_t = s_t C_t + D
    x_t``, a Python loop over the positions, one ``[E, N]`` state a sequence."""
    b, length, e = x.shape
    state, ys = jnp.zeros((b, e, A.shape[1])), []
    for t in range(length):
        state = (jnp.exp(dt[:, t, :, None] * A) * state
                 + (dt[:, t] * x[:, t])[..., None] * B[:, t, None, :])
        ys.append(jnp.sum(state * C[:, t, None, :], axis=-1) + D * x[:, t])
    return jnp.stack(ys, axis=1)


def _operands(b, length, e, n, seed=0, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(keys[0], (b, length, e), dtype),
            jax.nn.softplus(jax.random.normal(keys[1], (b, length, e)) - 2.0),
            -jnp.exp(0.5 * jax.random.normal(keys[2], (e, n))),
            jax.random.normal(keys[3], (b, length, n), dtype),
            jax.random.normal(keys[4], (b, length, n), dtype),
            jax.random.normal(keys[5], (e,)))


def _value_and_grads(fn, inputs):
    """``fn``'s result and the six gradients of a loss on it: one compiled
    program and one forward (eagerly the interpreted kernels and the token
    loop run operation by operation, the forward twice)."""
    def loss(*a):
        y = fn(*a)
        return jnp.sum(jnp.sin(y.astype(jnp.float32))), y
    (_, y), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(6)), has_aux=True))(*inputs)
    return y, grads


def _distance(a, b):
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("length,chunk", [(48, 16), (40, 16), (24, 32)],
                         ids=["whole-chunks", "ragged", "one-chunk"])
def test_plain_path_is_the_token_by_token_loop(length, chunk):
    inputs = _operands(2, length, 32, 4)
    want_y, want_g = _value_and_grads(token_loop, inputs)
    got_y, got_g = _value_and_grads(
        lambda *a: selective_scan(*a, chunk=chunk, impl="xla"), inputs)
    assert _distance(got_y, want_y) < 1e-5
    for name, got, want in zip(NAMES, got_g, want_g):
        assert got.shape == want.shape and _distance(got, want) < 1e-5, name


@pytest.mark.parametrize("length,e,dtype,tol", [
    (128, 1024, jnp.float32, 1e-5),    # two chunks: a boundary inside the sequence
    (96, 1024, jnp.float32, 1e-5),     # padded to two chunks
    (128, 1024, jnp.bfloat16, 1e-2),   # x, B, C as the model hands them
    # rows of [B, L, E] read where they lie: a bfloat16 tile packs 16 tokens, so
    # a length that is no multiple of 16 (padded to the chunk), alone and over
    # two channel tiles of a row; float32 x over two tiles and a ragged length
    (100, 1024, jnp.bfloat16, 1e-2),
    (72, 2048, jnp.bfloat16, 1e-2),
    (72, 2048, jnp.float32, 1e-5),
], ids=["two-chunks", "ragged", "bfloat16", "bfloat16-ragged-not-16",
        "bfloat16-two-tiles-ragged", "float32-two-tiles-ragged"])
def test_kernels_are_the_plain_path_value_and_all_six_gradients(length, e, dtype,
                                                                tol):
    inputs = _operands(2, length, e, 16, seed=1, dtype=dtype)
    want_y, want_g = _value_and_grads(
        lambda *a: selective_scan(*a, chunk=64, impl="xla"), inputs)
    got_y, got_g = _value_and_grads(
        lambda *a: selective_scan(*a, chunk=64, impl="pallas"), inputs)
    assert got_y.dtype == dtype and _distance(got_y, want_y) < tol
    for name, got, want in zip(NAMES, got_g, want_g):
        assert got.shape == want.shape and got.dtype == want.dtype, name
        assert _distance(got, want) < tol, name


def test_two_channel_tiles_and_sequences_share_nothing():
    """2,048 channels are two grid tiles (dB and dC are summed over them);
    a sequence's result does not depend on its neighbour in the batch."""
    inputs = _operands(2, 64, 2048, 16, seed=2)
    want_y, want_g = _value_and_grads(
        lambda *a: selective_scan(*a, chunk=64, impl="xla"), inputs)
    got_y, got_g = _value_and_grads(
        lambda *a: selective_scan(*a, chunk=64, impl="pallas"), inputs)
    assert _distance(got_y, want_y) < 1e-5
    for name, got, want in zip(NAMES, got_g, want_g):
        assert _distance(got, want) < 1e-5, name
    alone = tuple(t[:1] if t.ndim == 3 else t for t in inputs)
    np.testing.assert_allclose(
        jax.jit(lambda *a: selective_scan(*a, chunk=64, impl="pallas"))(*alone),
        got_y[:1], rtol=1e-6)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for inner in (param if isinstance(param, (list, tuple)) else [param]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _avals(jaxpr):
    """Every value of a jaxpr and of the jaxprs inside it."""
    for eqn in _eqns(jaxpr):
        yield from (v.aval for v in eqn.outvars)


@pytest.mark.parametrize("impl,e", [("xla", 128), ("pallas", 1024)])
def test_no_state_a_token_is_ever_built(impl, e):
    """Forward and backward hold the inputs and one ``[E, N]`` state a chunk:
    no value has ``L E N`` elements (a chunk's own states, ``Q E N``, are made
    again in its backward)."""
    b, length, n, chunk = 1, 256, 16, 64
    inputs = _operands(b, length, e, n)

    def loss(*a):
        return jnp.sum(selective_scan(*a, chunk=chunk, impl=impl))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=tuple(range(6))))(*inputs)
    largest = max(int(np.prod(a.shape)) for a in _avals(jaxpr.jaxpr)
                  if hasattr(a, "shape"))
    assert largest < length * e * n
    assert largest >= (length // chunk) * e * n       # the chunks' states
    _, residuals = ss._scan_fwd(*inputs, chunk, impl)
    assert [r.shape for r in residuals] == [t.shape for t in inputs] + [
        (b, length // chunk, e, n)]
    assert residuals[-1].dtype == jnp.float32


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_the_wide_operands_reach_the_kernels_as_the_rows_they_are(dtype):
    """``x``, ``dt``, ``dy`` go into the two kernels and ``y``, ``dx``, ``ddt``
    come out of them as ``[B, L, E]``: no value of the forward or the backward
    is one of them in another shape (``[B, L, E / 128, 128]`` is another
    tiling on the chip, a pass over memory each way)."""
    b, length, e, n = 2, 128, 2048, 16
    inputs = _operands(b, length, e, n, dtype=dtype)

    def loss(*a):
        return jnp.sum(selective_scan(*a, chunk=64, impl="pallas")
                       .astype(jnp.float32))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=tuple(range(6))))(*inputs)
    calls = [eqn for eqn in _eqns(jaxpr.jaxpr)
             if eqn.primitive.name == "pallas_call"]
    assert len(calls) == 2
    rows = (b, length, e)
    for eqn, wide_in, wide_out in zip(calls, (2, 3), (1, 2)):
        assert [v.aval.shape for v in eqn.invars].count(rows) == wide_in
        assert [v.aval.shape for v in eqn.outvars].count(rows) == wide_out
    assert not [a for a in _avals(jaxpr.jaxpr) if hasattr(a, "shape")
                and a.shape != rows and a.ndim > 3
                and int(np.prod(a.shape)) == b * length * e]


def test_gauges_say_what_a_call_moves_and_wrong_shapes_raise():
    inputs = _operands(1, 128, 1024, 16, dtype=jnp.bfloat16)
    selective_scan(*inputs, chunk=64, impl="xla")
    snap = telemetry.snapshot()
    tokens = 128 * 1024
    states = 2 * 1024 * 16 * 4
    assert snap["selective_scan.fwd.bytes"] == tokens * (2 + 2 + 4) + states
    assert snap["selective_scan.bwd.bytes"] == tokens * (3 * 2 + 2 * 4) + states
    assert (snap["selective_scan.chunks"], snap["selective_scan.channels"],
            snap["selective_scan.state"]) == (2, 1024, 16)
    x, dt, A, B, C, D = inputs
    with pytest.raises(ValueError, match="want"):
        selective_scan(x, dt[:, :-1], A, B, C, D)
    with pytest.raises(ValueError, match="multiple of 1024"):
        selective_scan(x[..., :512], dt[..., :512], A[:512], B, C, D[:512],
                       chunk=64, impl="pallas")
    with pytest.raises(ValueError, match="Unknown selective scan impl"):
        selective_scan(*inputs, impl="mosaic")


def test_kernels_run_per_device_under_a_mesh():
    """Four sequences over data=4: each device scans its own, ``A`` and ``D``
    arrive whole and their gradients are summed over the devices."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from autodist_tpu.parallel.mesh import build_mesh
    mesh = build_mesh(axes={"data": 4}, devices=jax.devices()[:4])
    inputs = _operands(4, 64, 1024, 16, seed=3)

    def loss(*a):
        return jnp.sum(jnp.sin(selective_scan(*a, chunk=64, impl="pallas")))

    want = jax.jit(jax.grad(loss, argnums=(0, 2, 5)))(*inputs)
    placed = tuple(jax.device_put(t, NamedSharding(
        mesh, P("data") if t.ndim == 3 else P())) for t in inputs)
    with mesh:
        got = jax.jit(jax.grad(loss, argnums=(0, 2, 5)))(*placed)
    for a, b in zip(got, want):
        assert _distance(a, b) < 1e-5
    assert len(got[0].sharding.device_set) == 4


def test_a_bfloat16_state_or_step_would_not_pass_these_tolerances():
    """The bound the benchmark's check cannot carry: at the cell's depth the
    whole model's gradient stands at 0.023 of the check's 0.05 with bfloat16
    products alone (0.049 before the first layer's output was made precise),
    and a scan whose step ``dt`` were bfloat16 moves it by half a percent of
    itself, one whose state were by a few percent at 256 positions
    (``tools/jamba_gradcheck.py``: 0.0459 -> 0.0461 / 0.0473 at 12 layers)
    and past the limit only at the cell's 16,384: not a failure the check
    shows at a size a test can run. Here both are failures: against the float32
    token loop the kernels stand at 1e-7 (the cases above hold them to 1e-5), a
    bfloat16 ``dt`` at 1.5e-3 in ``y`` and 7e-3 in the gradient the layers
    below receive, a bfloat16 state no nearer."""
    inputs = _operands(1, 128, 1024, 16, seed=4)
    x, dt, A, B, C, D = inputs
    bf16 = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731

    def rounded_state(x, dt, A, B, C, D):
        def token(state, row):
            x_t, dt_t, b_t, c_t = row
            state = bf16(jnp.exp(dt_t[..., None] * A) * state
                         + (dt_t * x_t)[..., None] * b_t[:, None, :])
            return state, jnp.sum(state * c_t[:, None, :], axis=-1) + D * x_t
        rows = tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, B, C))
        _, y = jax.lax.scan(token, jnp.zeros((1,) + A.shape), rows)
        return jnp.moveaxis(y, 0, 1)

    exact = lambda *a: selective_scan(*a, chunk=64, impl="xla")  # noqa: E731
    want_y, want_g = _value_and_grads(exact, inputs)
    kernel_y, kernel_g = _value_and_grads(
        lambda *a: selective_scan(*a, chunk=64, impl="pallas"), inputs)
    assert _distance(kernel_y, want_y) < 1e-5
    step_y, step_g = _value_and_grads(
        lambda x, dt, *rest: exact(x, bf16(dt), *rest), inputs)
    state_y, state_g = _value_and_grads(rounded_state, inputs)
    assert _distance(step_y, want_y) > 3e-4
    assert _distance(state_y, want_y) > 1e-3
    # the gradient with respect to x: what the layers below receive
    assert _distance(kernel_g[0], want_g[0]) < 1e-5
    assert _distance(step_g[0], want_g[0]) > 3e-4
    assert _distance(state_g[0], want_g[0]) > 1e-3
