"""Strategy x model-case x mesh-shape integration matrix.

The reference's integration tier ran the cartesian product {2 resource specs} x
{10 strategies} x {9 model cases} (``tests/integration/test_all.py:20-70``), with
cases covering placeholders, CNNs, sparse embeddings, ``while_loop`` models, and
dynamic RNNs. Same product here on the 8-device CPU-sim mesh: every strategy
family must train every case shape — dense MLP, conv net, sparse embedding,
PARTITIONED sparse embedding (uneven rows), ``lax.scan`` recurrence (the
while_loop analog), LSTM-style gated recurrence — on BOTH mesh shapes (pure
data-parallel, and a TP-capable ``{model: 2}`` mesh), each combo value-exact
against the single-process jit loss at step 0 and descending thereafter. No
forked processes needed: each combo builds a fresh AutoDist (the reference
needed a process per combo because its runtime was one-instance-per-process,
``test_all.py:49-70``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import AutoDist
from autodist_tpu.strategy import (AllReduce, AutoStrategy, Parallax, PartitionedAR,
                                   PartitionedPS, PS, PSLoadBalancing,
                                   RandomAxisPartitionAR, UnevenPartitionedPS)

BATCH = 16


# --------------------------------------------------------------------- cases

def _case_mlp():
    """Dense MLP on random regression (reference c0/c3: placeholder + numpy feeds)."""
    rng = np.random.RandomState(0)
    params = {
        "w1": jnp.asarray(rng.randn(12, 16) * 0.1, jnp.float32),
        "b1": jnp.zeros((16,)),
        "w2": jnp.asarray(rng.randn(16, 1) * 0.1, jnp.float32),
    }
    batch = {"x": rng.randn(BATCH, 12).astype(np.float32),
             "y": rng.randn(BATCH, 1).astype(np.float32)}

    def loss(p, b):
        h = jnp.tanh(b["x"] @ p["w1"] + p["b1"])
        return jnp.mean((h @ p["w2"] - b["y"]) ** 2)

    return params, batch, loss


def _case_cnn():
    """Tiny conv classifier (reference c1/c7: Keras image models)."""
    rng = np.random.RandomState(1)
    params = {
        "conv": jnp.asarray(rng.randn(3, 3, 1, 4) * 0.1, jnp.float32),
        "w": jnp.asarray(rng.randn(8 * 8 * 4, 10) * 0.1, jnp.float32),
        "b": jnp.zeros((10,)),
    }
    batch = {"x": rng.randn(BATCH, 8, 8, 1).astype(np.float32),
             "y": rng.randint(0, 10, size=(BATCH,))}

    def loss(p, b):
        h = jax.lax.conv_general_dilated(
            b["x"], p["conv"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        h = jax.nn.relu(h).reshape(b["x"].shape[0], -1)
        logits = h @ p["w"] + p["b"]
        return -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(logits.shape[0]), b["y"]])

    return params, batch, loss


def _case_embedding():
    """Sparse embedding lookup (reference c2: sentiment / sparse grads)."""
    rng = np.random.RandomState(2)
    params = {
        "emb": jnp.asarray(rng.randn(40, 8) * 0.1, jnp.float32),
        "w": jnp.asarray(rng.randn(8, 1) * 0.1, jnp.float32),
    }
    batch = {"idx": rng.randint(0, 40, size=(BATCH, 5)),
             "y": rng.randn(BATCH, 1).astype(np.float32)}

    def loss(p, b):
        e = jnp.take(p["emb"], b["idx"], axis=0).mean(axis=1)
        return jnp.mean((e @ p["w"] - b["y"]) ** 2)

    return params, batch, loss


def _case_scan_rnn():
    """lax.scan recurrence — the while_loop model (reference c4)."""
    rng = np.random.RandomState(3)
    params = {
        "w_in": jnp.asarray(rng.randn(4, 8) * 0.3, jnp.float32),
        "w_rec": jnp.asarray(rng.randn(8, 8) * 0.1, jnp.float32),
        "w_out": jnp.asarray(rng.randn(8, 1) * 0.3, jnp.float32),
    }
    batch = {"x": rng.randn(BATCH, 6, 4).astype(np.float32),
             "y": rng.randn(BATCH, 1).astype(np.float32)}

    def loss(p, b):
        def cell(h, x_t):
            h = jnp.tanh(x_t @ p["w_in"] + h @ p["w_rec"])
            return h, None

        h0 = jnp.zeros((b["x"].shape[0], 8))
        h, _ = jax.lax.scan(cell, h0, b["x"].transpose(1, 0, 2))
        return jnp.mean((h @ p["w_out"] - b["y"]) ** 2)

    return params, batch, loss


def _case_lstm():
    """Gated (LSTM-style) recurrence (reference c6: dynamic LSTM)."""
    rng = np.random.RandomState(4)
    d_in, d_h = 4, 8
    params = {
        "w": jnp.asarray(rng.randn(d_in + d_h, 4 * d_h) * 0.2, jnp.float32),
        "b": jnp.zeros((4 * d_h,)),
        "w_out": jnp.asarray(rng.randn(d_h, 1) * 0.3, jnp.float32),
    }
    batch = {"x": rng.randn(BATCH, 5, d_in).astype(np.float32),
             "y": rng.randn(BATCH, 1).astype(np.float32)}

    def loss(p, b):
        def cell(carry, x_t):
            h, c = carry
            z = jnp.concatenate([x_t, h], axis=-1) @ p["w"] + p["b"]
            i, f, g, o = jnp.split(z, 4, axis=-1)
            c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), None

        h0 = jnp.zeros((b["x"].shape[0], d_h))
        (h, _), _ = jax.lax.scan(cell, (h0, h0), b["x"].transpose(1, 0, 2))
        return jnp.mean((h @ p["w_out"] - b["y"]) ** 2)

    return params, batch, loss


def _case_partitioned_embedding():
    """LARGE sparse embedding with a prime row count (reference c2 at the
    partitioner's scale): partitioning strategies must split the table —
    unevenly, 1031 doesn't divide — while the gradient stays sparse."""
    rng = np.random.RandomState(5)
    params = {
        "emb": jnp.asarray(rng.randn(1031, 16) * 0.1, jnp.float32),
        "w": jnp.asarray(rng.randn(16, 1) * 0.1, jnp.float32),
    }
    batch = {"idx": rng.randint(0, 1031, size=(BATCH, 6)),
             "y": rng.randn(BATCH, 1).astype(np.float32)}

    def loss(p, b):
        e = jnp.take(p["emb"], b["idx"], axis=0).mean(axis=1)
        return jnp.mean((e @ p["w"] - b["y"]) ** 2)

    return params, batch, loss


CASES = {
    "mlp": _case_mlp,
    "cnn": _case_cnn,
    "embedding": _case_embedding,
    "part_embedding": _case_partitioned_embedding,
    "scan_rnn": _case_scan_rnn,
    "lstm": _case_lstm,
}

STRATEGIES = [
    PS, PSLoadBalancing, PartitionedPS, UnevenPartitionedPS,
    AllReduce, PartitionedAR, RandomAxisPartitionAR, Parallax, AutoStrategy,
]

# Two mesh shapes, the reference's {2 resource specs} dimension: the default
# pure-data mesh, and a TP-capable mesh with a non-trivial model axis.
MESHES = {
    "data8": None,
    "model2": "{nodes: [{address: localhost, tpus: 8}], mesh: {model: 2}}",
}


@pytest.mark.parametrize("mesh_name", list(MESHES), ids=str)
@pytest.mark.parametrize("case_name", list(CASES), ids=str)
@pytest.mark.parametrize("builder_cls", STRATEGIES, ids=lambda c: c.__name__)
def test_strategy_times_case(builder_cls, case_name, mesh_name):
    params, batch, loss = CASES[case_name]()
    # Value-exactness anchor: whatever the strategy/mesh does, step 0's loss
    # must equal the plain single-process jit loss on the same params/batch
    # (the reference's c0 criterion).
    expected0 = float(jax.jit(loss)(params, {k: jnp.asarray(v)
                                             for k, v in batch.items()}))
    ad = AutoDist(MESHES[mesh_name], strategy_builder=builder_cls())
    step = ad.function(loss, params, optax.adam(3e-2), example_batch=batch)
    losses = [float(step(batch)) for _ in range(8)]
    np.testing.assert_allclose(losses[0], expected0, rtol=1e-5, atol=1e-6,
                               err_msg=f"{builder_cls.__name__}/{case_name}/"
                                       f"{mesh_name}")
    assert np.all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], (builder_cls.__name__, case_name, losses)
    final = step.get_state().params
    assert all(np.all(np.isfinite(np.asarray(v)))
               for v in jax.tree_util.tree_leaves(final))


@pytest.mark.parametrize("case_name", list(CASES), ids=str)
@pytest.mark.parametrize("builder_cls", [AllReduce, PartitionedPS, Parallax],
                         ids=lambda c: c.__name__)
def test_strategy_times_case_with_accumulation(builder_cls, case_name):
    """The micro-batch scan must compose with every case shape (BATCH=16 splits
    into 2 micro-batches over the 8-device mesh)."""
    params, batch, loss = CASES[case_name]()
    ad = AutoDist(strategy_builder=builder_cls())
    step = ad.function(loss, params, optax.adam(3e-2), example_batch=batch,
                       accumulation_steps=2)
    losses = [float(step(batch)) for _ in range(8)]
    assert np.all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], (builder_cls.__name__, case_name, losses)
