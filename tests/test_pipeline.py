"""Pipeline parallelism: GPipe + 1F1B loop correctness, gradients, memory,
strategy, e2e training."""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from autodist_tpu import AutoDist, ResourceSpec
from autodist_tpu.model_spec import ModelSpec
from autodist_tpu.models import pipeline_lm
from autodist_tpu.parallel.pipeline import pipelined, pipelined_value_and_grad
from autodist_tpu.parallel.plan import ShardingPlan
from autodist_tpu.strategy import Pipeline, StrategyCompiler

TINY = pipeline_lm.PipelineLMConfig(
    vocab_size=64, d_model=16, n_heads=2, n_layers=4, d_ff=32, max_len=32,
    n_stages=4, num_microbatches=4, dtype=jnp.float32)


def _spec_for(n_devices=8, mesh=None):
    return ResourceSpec(resource_info={
        "nodes": [{"address": "localhost", "tpus": n_devices, "chief": True}],
        **({"mesh": mesh} if mesh else {}),
    })


def _pipe_mesh(n_stages=4):
    from autodist_tpu.parallel.mesh import build_mesh
    return build_mesh(axes={"pipe": n_stages, "data": -1})


def test_gpipe_loop_matches_sequential_forward_and_grad():
    rng = np.random.RandomState(0)
    d, s, m = 8, 4, 6
    w = (rng.randn(s, d, d) * 0.3).astype(np.float32)
    x_mb = rng.randn(m, 4, d).astype(np.float32)
    mesh = _pipe_mesh(s)

    def stage_fn(p, x):
        return jnp.tanh(x @ p[0])

    f = pipelined(stage_fn, s, mesh=mesh)

    def loss_pipe(w, x):
        return (f(w, x) ** 2).sum()

    def loss_seq(w, x):
        h = x
        for i in range(s):
            h = jnp.tanh(h @ w[i])
        return (h ** 2).sum()

    with mesh:
        lp, gp = jax.jit(jax.value_and_grad(loss_pipe))(w, x_mb)
        ls, gs = jax.jit(jax.value_and_grad(loss_seq))(w, x_mb)
    np.testing.assert_allclose(float(lp), float(ls), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gs), rtol=1e-4, atol=1e-5)


def _onef_oneb_setup(s=4, m=6, d=8, seed=0):
    rng = np.random.RandomState(seed)
    w = (rng.randn(s, d, d) * 0.3).astype(np.float32)
    head = (rng.randn(d, 3) * 0.3).astype(np.float32)
    x_mb = rng.randn(m, 4, d).astype(np.float32)
    t_mb = rng.randn(m, 4, 3).astype(np.float32)

    def stage_fn(p, x):
        return jnp.tanh(x @ p[0])

    def tail_fn(tp, y, tgt):
        return jnp.mean((y @ tp - tgt) ** 2)

    return w, head, x_mb, t_mb, stage_fn, tail_fn


def test_onef_oneb_matches_gpipe_loss_and_grads():
    """1F1B returns the SAME mean loss and gradients (stage, tail, input) as
    GPipe + autodiff on the same stages — only the schedule differs."""
    s, m = 4, 6
    w, head, x_mb, t_mb, stage_fn, tail_fn = _onef_oneb_setup(s, m)
    mesh = _pipe_mesh(s)

    f_1f1b = pipelined_value_and_grad(stage_fn, tail_fn, s, mesh=mesh)
    gpipe = pipelined(stage_fn, s, mesh=mesh)

    def gpipe_loss(w, head, x, tgt):
        y = gpipe(w, x)
        losses = jax.vmap(lambda yk, tk: tail_fn(head, yk, tk))(y, tgt)
        return losses.mean()

    with mesh:
        loss_b, gs_b, gt_b, gx_b = jax.jit(f_1f1b)(w, head, x_mb, t_mb)
        loss_a, (gs_a, gt_a, gx_a) = jax.jit(jax.value_and_grad(
            gpipe_loss, argnums=(0, 1, 2)))(w, head, x_mb, t_mb)
    np.testing.assert_allclose(float(loss_b), float(loss_a), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gs_b), np.asarray(gs_a),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gt_b), np.asarray(gt_a),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gx_b), np.asarray(gx_a),
                               rtol=1e-4, atol=1e-6)


def test_onef_oneb_single_stage_degenerate():
    w, head, x_mb, t_mb, stage_fn, tail_fn = _onef_oneb_setup(s=1, m=4)
    from autodist_tpu.parallel.mesh import build_mesh
    mesh = build_mesh(axes={"pipe": 1, "data": -1})
    f = pipelined_value_and_grad(stage_fn, tail_fn, 1, mesh=mesh)

    def ref(w, head, x, tgt):
        y = jax.vmap(lambda xk: stage_fn(w, xk))(x)
        return jax.vmap(lambda yk, tk: tail_fn(head, yk, tk))(y, tgt).mean()

    with mesh:
        loss, gs, gt, gx = jax.jit(f)(w, head, x_mb, t_mb)
        l_ref, (gs_r, gt_r, gx_r) = jax.jit(jax.value_and_grad(
            ref, argnums=(0, 1, 2)))(w, head, x_mb, t_mb)
    np.testing.assert_allclose(float(loss), float(l_ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(gs_r), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gt), np.asarray(gt_r), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_r), rtol=1e-4)


def test_onef_oneb_memory_flat_in_microbatches():
    """The point of 1F1B: compiled temp memory stays ~flat as num_microbatches
    grows (live set O(n_stages)), while GPipe+autodiff's grows linearly
    (residuals for every tick)."""
    s, d = 4, 64
    mesh = _pipe_mesh(s)

    def measure(m):
        w, head, x_mb, t_mb, stage_fn, tail_fn = _onef_oneb_setup(s, m, d)
        f_1f1b = pipelined_value_and_grad(stage_fn, tail_fn, s, mesh=mesh)
        gpipe = pipelined(stage_fn, s, mesh=mesh)

        def gpipe_loss(w, head, x, tgt):
            y = gpipe(w, x)
            return jax.vmap(lambda yk, tk: tail_fn(head, yk, tk))(y, tgt).mean()

        with mesh:
            mem_b = jax.jit(f_1f1b).lower(w, head, x_mb, t_mb).compile() \
                .memory_analysis().temp_size_in_bytes
            mem_a = jax.jit(jax.value_and_grad(gpipe_loss, argnums=(0, 1))) \
                .lower(w, head, x_mb, t_mb).compile() \
                .memory_analysis().temp_size_in_bytes
        return mem_a, mem_b

    gpipe_4, onef_4 = measure(4)
    gpipe_32, onef_32 = measure(32)
    # GPipe's residual storage scales with the microbatch count (measured on
    # this config: 49.7 KB -> 193.2 KB over 4 -> 32 microbatches)...
    assert gpipe_32 > 3 * gpipe_4, (gpipe_4, gpipe_32)
    # ...1F1B's live set does not (measured ~30.4 KB -> ~33.8 KB: the ring is
    # sized by n_stages; slack covers the [M, ...] input-grad buffer).
    assert onef_32 < 1.5 * onef_4, (onef_4, onef_32)
    assert onef_32 < gpipe_32 / 4, (onef_32, gpipe_32)


def test_pipeline_lm_onef_oneb_full_model_grads():
    """The full-model 1F1B step returns the SAME loss and gradients — for
    embedding, positions, every block, final norm, and head — as
    jax.value_and_grad over the GPipe loss."""
    model, params = pipeline_lm.init_params(TINY)
    batch = pipeline_lm.synthetic_batch(TINY, batch_size=8, seq_len=16)
    mesh = _pipe_mesh(TINY.n_stages)

    f_1f1b = pipeline_lm.make_onef_oneb_value_and_grad(model)
    loss_fn = pipeline_lm.make_loss_fn(model)
    with mesh:
        loss_b, grads_b = jax.jit(f_1f1b)(params, batch)
        loss_a, grads_a = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
    np.testing.assert_allclose(float(loss_b), float(loss_a), rtol=1e-5)
    flat_a = jax.tree_util.tree_leaves_with_path(grads_a)
    flat_b = dict(jax.tree_util.tree_leaves_with_path(grads_b))
    assert len(flat_a) == len(flat_b)
    for path, g in flat_a:
        np.testing.assert_allclose(
            np.asarray(flat_b[path]), np.asarray(g), rtol=2e-4, atol=1e-6,
            err_msg=jax.tree_util.keystr(path))
    # And a few SGD steps actually train.
    import optax
    opt = optax.sgd(0.1)
    state = opt.init(params)
    losses = []
    with mesh:
        for _ in range(5):
            loss, grads = jax.jit(f_1f1b)(params, batch)
            updates, state = opt.update(grads, state, params)
            params = optax.apply_updates(params, updates)
            losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_pipeline_lm_matches_sequential_apply():
    model, params = pipeline_lm.init_params(TINY)
    batch = pipeline_lm.synthetic_batch(TINY, batch_size=8, seq_len=16)
    tokens = jnp.asarray(batch["tokens"][:, :-1])
    mesh = _pipe_mesh(TINY.n_stages)
    with mesh:
        piped = jax.jit(model.apply)(params, tokens)
    seq = pipeline_lm.sequential_apply(model, params, tokens)
    np.testing.assert_allclose(np.asarray(piped), np.asarray(seq),
                               rtol=2e-4, atol=2e-4)


def test_pipeline_strategy_shards_block_stacks():
    model, params = pipeline_lm.init_params(TINY)
    model_spec = ModelSpec.from_params(params)
    rs = _spec_for(8)
    strategy = StrategyCompiler(model_spec, rs).compile(
        Pipeline(n_stages=4).build(model_spec, rs))
    assert strategy.mesh_axes()["pipe"] == 4
    assert strategy.mesh_axes()["data"] == 2

    plan = ShardingPlan.from_strategy(strategy, model_spec)
    block_plans = [p for n, p in plan.params.items() if "blocks" in n]
    assert len(block_plans) == 8
    for p in block_plans:
        assert p.partition_mesh_axis == "pipe"
        assert p.pspec[0] == "pipe"
    assert plan.params["embed"].pspec == jax.sharding.PartitionSpec()


def test_pipeline_lm_trains_end_to_end():
    model, params = pipeline_lm.init_params(TINY)
    loss_fn = pipeline_lm.make_loss_fn(model)
    batch = pipeline_lm.synthetic_batch(TINY, batch_size=8, seq_len=16)
    ad = AutoDist(_spec_for(8), strategy_builder=Pipeline(n_stages=4))
    step = ad.function(loss_fn, params, optax.adam(1e-2), example_batch=batch)
    losses = [float(step(batch)) for _ in range(4)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]

    # Block stacks live sharded over the pipe axis.
    state = step.get_state()
    spec = state.params["blocks"]["wqkv"].sharding.spec
    assert spec and spec[0] == "pipe"


def test_pipeline_e2e_loss_matches_unsharded():
    model, params = pipeline_lm.init_params(TINY)
    loss_fn = pipeline_lm.make_loss_fn(model)
    batch = pipeline_lm.synthetic_batch(TINY, batch_size=8, seq_len=16)

    def seq_loss(params, batch):
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        logits = pipeline_lm.sequential_apply(model, params, inputs)
        logprobs = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(
            logprobs, targets[..., None], axis=-1)[..., 0].mean()

    expected = float(seq_loss(params, {k: jnp.asarray(v) for k, v in batch.items()}))
    ad = AutoDist(_spec_for(8), strategy_builder=Pipeline(n_stages=4))
    step = ad.function(loss_fn, params, optax.sgd(0.0), example_batch=batch)
    np.testing.assert_allclose(float(step(batch)), expected, rtol=2e-5)


def test_pipelined_rejects_mesh_stage_mismatch():
    import pytest
    mesh = _pipe_mesh(2)
    f = pipelined(lambda p, x: x, n_stages=4, mesh=mesh)
    with mesh, pytest.raises(ValueError, match="pipe"):
        jax.jit(lambda w, x: f(w, x))(jnp.zeros((4, 2, 2)), jnp.zeros((2, 2, 2)))


def test_interleaved_matches_plain_1f1b():
    """Interleaved 1F1B (v chunks per device) returns the SAME loss and
    gradients as plain 1F1B run with one device per virtual stage — only the
    device mapping and schedule differ."""
    from autodist_tpu.parallel.mesh import build_mesh
    from autodist_tpu.parallel.pipeline import (interleave_chunk_layout,
                                                interleaved_value_and_grad)
    s, v, m = 2, 2, 6
    V = s * v
    w, head, x_mb, t_mb, stage_fn, tail_fn = _onef_oneb_setup(V, m, seed=2)

    plain_mesh = build_mesh(axes={"pipe": V, "data": -1})
    f_plain = pipelined_value_and_grad(stage_fn, tail_fn, V, mesh=plain_mesh)
    with plain_mesh:
        loss_p, gs_p, gt_p, gx_p = jax.jit(f_plain)(w, head, x_mb, t_mb)

    il_mesh = build_mesh(axes={"pipe": s, "data": -1})
    f_il = interleaved_value_and_grad(stage_fn, tail_fn, s, v, mesh=il_mesh)
    w_dev = interleave_chunk_layout(w, s, v)          # virtual -> device-major
    with il_mesh:
        loss_i, gs_i, gt_i, gx_i = jax.jit(f_il)(w_dev, head, x_mb, t_mb)
    gs_i = interleave_chunk_layout(gs_i, s, v, inverse=True)

    np.testing.assert_allclose(float(loss_i), float(loss_p), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gs_i), np.asarray(gs_p),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gt_i), np.asarray(gt_p),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gx_i), np.asarray(gx_p),
                               rtol=1e-4, atol=1e-6)


def test_interleaved_deeper_and_chunks_one_degenerates():
    """v=4 chunks on 2 devices (8 virtual stages); and n_chunks=1 must equal
    plain 1F1B exactly (same schedule by construction)."""
    from autodist_tpu.parallel.mesh import build_mesh
    from autodist_tpu.parallel.pipeline import (interleave_chunk_layout,
                                                interleaved_value_and_grad)
    s, v, m = 2, 4, 4
    V = s * v
    w, head, x_mb, t_mb, stage_fn, tail_fn = _onef_oneb_setup(V, m, seed=5)
    mesh = build_mesh(axes={"pipe": s, "data": -1})
    f_il = interleaved_value_and_grad(stage_fn, tail_fn, s, v, mesh=mesh)
    with mesh:
        loss_i, gs_i, _, gx_i = jax.jit(f_il)(
            interleave_chunk_layout(w, s, v), head, x_mb, t_mb)
    gs_i = interleave_chunk_layout(gs_i, s, v, inverse=True)

    # Sequential oracle over all V stages.
    def ref(w, head, x, tgt):
        def one(xk, tk):
            h = xk
            for i in range(V):
                h = stage_fn(w[i:i + 1], h)   # stage_fn takes a [1, ...] block
            return tail_fn(head, h, tk)
        return jax.vmap(one)(x, tgt).mean()
    l_ref, (gs_r, gx_r) = jax.jit(jax.value_and_grad(
        ref, argnums=(0, 2)))(w, head, x_mb, t_mb)
    np.testing.assert_allclose(float(loss_i), float(l_ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gs_i), np.asarray(gs_r),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gx_i), np.asarray(gx_r),
                               rtol=1e-4, atol=1e-6)

    # n_chunks=1: identical schedule to plain 1F1B.
    s4 = 4
    w4, head4, x4, t4, stage_fn, tail_fn = _onef_oneb_setup(s4, 4, seed=7)
    mesh4 = build_mesh(axes={"pipe": s4, "data": -1})
    f_plain = pipelined_value_and_grad(stage_fn, tail_fn, s4, mesh=mesh4)
    f_one = interleaved_value_and_grad(stage_fn, tail_fn, s4, 1, mesh=mesh4)
    with mesh4:
        loss_p, gs_p, gt_p, gx_p = jax.jit(f_plain)(w4, head4, x4, t4)
        loss_o, gs_o, gt_o, gx_o = jax.jit(f_one)(w4, head4, x4, t4)
    np.testing.assert_allclose(float(loss_o), float(loss_p), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gs_o), np.asarray(gs_p), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gt_o), np.asarray(gt_p), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gx_o), np.asarray(gx_p), rtol=1e-5)


def test_interleaved_wide_mesh_and_validation():
    """S=4 with v=2 (wide mesh x chunks); non-divisible microbatch counts are
    refused (a ragged final group would silently skip/double-process pairs);
    scalar stage-params leaves get the clear leading-dim error."""
    import pytest

    from autodist_tpu.parallel.mesh import build_mesh
    from autodist_tpu.parallel.pipeline import (interleave_chunk_layout,
                                                interleaved_value_and_grad)
    s, v, m = 4, 2, 8
    V = s * v
    w, head, x_mb, t_mb, stage_fn, tail_fn = _onef_oneb_setup(V, m, seed=9)
    mesh = build_mesh(axes={"pipe": s, "data": -1})
    f_il = interleaved_value_and_grad(stage_fn, tail_fn, s, v, mesh=mesh)
    with mesh:
        loss_i, gs_i, _, gx_i = jax.jit(f_il)(
            interleave_chunk_layout(w, s, v), head, x_mb, t_mb)
    gs_i = interleave_chunk_layout(gs_i, s, v, inverse=True)

    def ref(w, head, x, tgt):
        def one(xk, tk):
            h = xk
            for i in range(V):
                h = stage_fn(w[i:i + 1], h)
            return tail_fn(head, h, tk)
        return jax.vmap(one)(x, tgt).mean()
    l_ref, (gs_r, gx_r) = jax.jit(jax.value_and_grad(
        ref, argnums=(0, 2)))(w, head, x_mb, t_mb)
    np.testing.assert_allclose(float(loss_i), float(l_ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gs_i), np.asarray(gs_r),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gx_i), np.asarray(gx_r),
                               rtol=1e-4, atol=1e-6)

    with mesh, pytest.raises(ValueError, match="divisible by n_stages"):
        jax.jit(f_il)(interleave_chunk_layout(w, s, v), head,
                      x_mb[:5], t_mb[:5])
    with mesh, pytest.raises(ValueError, match="leading dim"):
        jax.jit(f_il)({"w": interleave_chunk_layout(w, s, v),
                       "gain": jnp.ones(())}, head, x_mb, t_mb)


def test_blocks_execution_order_roundtrip():
    """Stored (device-major) <-> execution-order conversion round-trips, and
    sequential_apply(interleaved cfg) equals the n_chunks=1 model applied to
    the execution-order blocks — the checkpoint-migration contract."""
    cfg = pipeline_lm.PipelineLMConfig(
        vocab_size=64, d_model=16, n_heads=2, n_layers=4, d_ff=32, max_len=32,
        n_stages=2, n_chunks=2, num_microbatches=2, dtype=jnp.float32)
    model, params = pipeline_lm.init_params(cfg)
    exe = pipeline_lm.blocks_to_execution_order(cfg, params["blocks"])
    back = pipeline_lm.blocks_from_execution_order(cfg, exe)
    for path, a in jax.tree_util.tree_leaves_with_path(params["blocks"]):
        b = dict(jax.tree_util.tree_leaves_with_path(back))[path]
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    import dataclasses
    plain_model = pipeline_lm.PipelineLM(dataclasses.replace(cfg, n_chunks=1))
    plain_params = dict(params, blocks=exe)
    toks = jnp.asarray(pipeline_lm.synthetic_batch(cfg, 4, 8)["tokens"][:, :-1])
    a = pipeline_lm.sequential_apply(model, params, toks)
    b = pipeline_lm.sequential_apply(plain_model, plain_params, toks)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    # And the GPipe pipeline forward (model.apply) honors the stored layout:
    # it must equal sequential_apply on the SAME interleaved config.
    mesh = _pipe_mesh(cfg.n_stages)
    with mesh:
        c = jax.jit(lambda p, t: model.apply(p, t))(params, toks)
    np.testing.assert_allclose(np.asarray(c), np.asarray(a),
                               rtol=1e-4, atol=1e-5)



def test_interleave_chunk_layout_roundtrip():
    from autodist_tpu.parallel.pipeline import interleave_chunk_layout
    x = jnp.arange(6 * 3).reshape(6, 3)           # V=6 rows
    fwd = interleave_chunk_layout(x, n_stages=3, n_chunks=2)
    # Device-major: row r*v + j = virtual j*S + r.
    expect = [0 * 3 + 0, 1 * 3 + 0, 0 * 3 + 1, 1 * 3 + 1, 0 * 3 + 2, 1 * 3 + 2]
    np.testing.assert_array_equal(np.asarray(fwd[:, 0]) // 3, expect)
    back = interleave_chunk_layout(fwd, n_stages=3, n_chunks=2, inverse=True)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


def test_pipeline_lm_interleaved_full_model_grads():
    """The full-model INTERLEAVED step (n_chunks=2: 4 layers as 4 virtual
    stages on 2 devices) returns the same loss and gradients as autodiff
    over the sequential forward — same surface, thinner-tick schedule."""
    cfg = pipeline_lm.PipelineLMConfig(
        vocab_size=64, d_model=16, n_heads=2, n_layers=4, d_ff=32, max_len=32,
        n_stages=2, n_chunks=2, num_microbatches=4, dtype=jnp.float32)
    model, params = pipeline_lm.init_params(cfg)
    batch = pipeline_lm.synthetic_batch(cfg, batch_size=8, seq_len=16)
    mesh = _pipe_mesh(cfg.n_stages)

    f_il = pipeline_lm.make_onef_oneb_value_and_grad(model)

    def seq_loss(params, batch):
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        logits = pipeline_lm.sequential_apply(model, params, inputs)
        logprobs = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(
            logprobs, targets[..., None], axis=-1)[..., 0].mean()

    with mesh:
        loss_i, grads_i = jax.jit(f_il)(params, batch)
    loss_s, grads_s = jax.jit(jax.value_and_grad(seq_loss))(params, batch)
    np.testing.assert_allclose(float(loss_i), float(loss_s), rtol=1e-5)
    flat_s = jax.tree_util.tree_leaves_with_path(grads_s)
    flat_i = dict(jax.tree_util.tree_leaves_with_path(grads_i))
    for path, g in flat_s:
        np.testing.assert_allclose(
            np.asarray(flat_i[path]), np.asarray(g), rtol=2e-4, atol=1e-6,
            err_msg=jax.tree_util.keystr(path))

    import pytest
    with pytest.raises(ValueError, match="num_microbatches"):
        pipeline_lm.PipelineLMConfig(
            vocab_size=64, d_model=16, n_heads=2, n_layers=4, d_ff=32,
            n_stages=2, n_chunks=2, num_microbatches=3, dtype=jnp.float32)
