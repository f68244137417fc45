"""Checkpoint suites — parity with reference tests/checkpoint/* and c0's assertions:
original-name checkpoints, cross-strategy restore, rotation, serving export."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import AutoDist
from autodist_tpu.checkpoint import SavedModelBuilder, Saver
from autodist_tpu.strategy import AllReduce, PartitionedPS, PS


def _loss(p, batch):
    pred = batch["x"] @ p["dense"]["w"] + p["dense"]["b"]
    return jnp.mean((batch["y"] - pred) ** 2)


def _params():
    rng = np.random.RandomState(7)
    return {"dense": {"w": jnp.asarray(rng.randn(16, 4), jnp.float32),
                      "b": jnp.zeros((4,))}}


def _batch():
    rng = np.random.RandomState(1)
    return {"x": rng.randn(32, 16).astype(np.float32),
            "y": rng.randn(32, 4).astype(np.float32)}


def _train(builder, n_steps, params, batch):
    ad = AutoDist(strategy_builder=builder)
    runner = ad.create_distributed_session(_loss, params, optax.adam(1e-2),
                                           example_batch=batch)
    state = runner.init(params)
    for _ in range(n_steps):
        state, _ = runner.run(state, batch)
    return runner, state


def test_save_restores_original_names(tmp_path):
    runner, state = _train(PartitionedPS(), 2, _params(), _batch())
    saver = Saver()
    prefix = saver.save(state, str(tmp_path / "ckpt"))
    flat = dict(np.load(prefix + ".npz"))
    # Original single-node names, full logical shapes — no shard suffixes.
    assert "dense/w" in flat and flat["dense/w"].shape == (16, 4)
    assert "dense/b" in flat
    assert not any("part_" in k for k in flat)


def test_cross_strategy_restore_value_equality(tmp_path):
    """Train under PartitionedPS, save, restore into AllReduce: parameters equal
    (reference restored PartitionedPS checkpoints into vanilla TF the same way)."""
    batch = _batch()
    runner_a, state_a = _train(PartitionedPS(), 3, _params(), batch)
    saver = Saver()
    prefix = saver.save(state_a, str(tmp_path / "ckpt"))

    ad_b = AutoDist(strategy_builder=AllReduce())
    runner_b = ad_b.create_distributed_session(_loss, _params(), optax.adam(1e-2),
                                               example_batch=batch)
    state_b = saver.restore(prefix, runner=runner_b)
    for name in ("w", "b"):
        np.testing.assert_allclose(
            np.asarray(jax.device_get(state_a.params["dense"][name])),
            np.asarray(jax.device_get(state_b.params["dense"][name])), rtol=1e-6)
    # optimizer state also restored
    mu_a = jax.tree_util.tree_leaves(state_a.opt_state)
    mu_b = jax.tree_util.tree_leaves(state_b.opt_state)
    for a, b in zip(mu_a, mu_b):
        np.testing.assert_allclose(np.asarray(jax.device_get(a)),
                                   np.asarray(jax.device_get(b)), rtol=1e-6)
    assert int(np.asarray(state_b.step)) == 3


def test_resume_training_continues_identically(tmp_path):
    """Save at step 2, restore, run 2 more: identical to 4 uninterrupted steps."""
    batch = _batch()
    runner, state = _train(PS(), 2, _params(), batch)
    saver = Saver()
    prefix = saver.save(state, str(tmp_path / "ckpt"))

    for _ in range(2):
        state, _ = runner.run(state, batch)

    ad2 = AutoDist(strategy_builder=PS())
    runner2 = ad2.create_distributed_session(_loss, _params(), optax.adam(1e-2),
                                             example_batch=batch)
    state2 = saver.restore(prefix, runner=runner2)
    for _ in range(2):
        state2, _ = runner2.run(state2, batch)

    np.testing.assert_allclose(
        np.asarray(jax.device_get(state.params["dense"]["w"])),
        np.asarray(jax.device_get(state2.params["dense"]["w"])), rtol=1e-6)


def test_restore_to_host_numpy_without_runner(tmp_path):
    runner, state = _train(PS(), 1, _params(), _batch())
    prefix = Saver().save(state, str(tmp_path / "ckpt"))
    params = Saver().restore_params(prefix)
    assert set(params) == {"dense"}
    assert params["dense"]["w"].shape == (16, 4)
    np.testing.assert_allclose(
        params["dense"]["w"],
        np.asarray(jax.device_get(state.params["dense"]["w"])))


def test_latest_checkpoint_and_rotation(tmp_path):
    saver = Saver(max_to_keep=2)
    params = _params()
    for step in range(4):
        saver.save(params, str(tmp_path / "ckpt"), global_step=step)
    latest = Saver.latest_checkpoint(str(tmp_path))
    assert latest.endswith("ckpt-3")
    remaining = sorted(p for p in os.listdir(tmp_path) if p.endswith(".npz"))
    assert remaining == ["ckpt-2.npz", "ckpt-3.npz"]


def test_missing_param_raises(tmp_path):
    prefix = Saver().save({"w": jnp.zeros((2,))}, str(tmp_path / "ckpt"))
    ad = AutoDist(strategy_builder=AllReduce())
    runner = ad.create_distributed_session(_loss, _params(), optax.sgd(0.1),
                                           example_batch=_batch())
    with pytest.raises(KeyError, match="dense/"):
        Saver().restore(prefix, runner=runner)


def test_saved_model_export_roundtrip(tmp_path):
    params = _params()
    export_dir = str(tmp_path / "serve")
    builder = SavedModelBuilder(export_dir)

    def apply_fn(p, x):
        return x @ p["dense"]["w"] + p["dense"]["b"]

    x = np.asarray(np.random.RandomState(5).randn(2, 16), np.float32)
    builder.save(params, model_config={"kind": "linear"}, apply_fn=apply_fn,
                 example_args=(x,))
    assert os.path.exists(os.path.join(export_dir, "params.npz"))
    assert os.path.exists(os.path.join(export_dir, "apply.hlo"))
    assert os.path.exists(os.path.join(export_dir, "apply.export"))
    loaded = SavedModelBuilder.load_params(export_dir)
    np.testing.assert_allclose(loaded["dense"]["w"],
                               np.asarray(params["dense"]["w"]))
    # The artifact EXECUTES: deserialize apply.export and serve it against the
    # reloaded params, matching the live apply fn (reference proved its export
    # by serving the SavedModel in vanilla TF, test_saved_model.py:26-40).
    serve = SavedModelBuilder.load_serving_fn(export_dir)
    np.testing.assert_allclose(np.asarray(serve(loaded, x)),
                               np.asarray(apply_fn(params, x)),
                               rtol=1e-6, atol=1e-6)
    # Re-saving WITHOUT apply_fn must sweep the executable graph: serving a
    # stale apply.export against replaced params is silent wrong output.
    builder.save(params, model_config={"kind": "linear"})
    assert not os.path.exists(os.path.join(export_dir, "apply.export"))
    assert not os.path.exists(os.path.join(export_dir, "apply.hlo"))


def test_saved_model_serves_without_model_code(tmp_path):
    """A fresh process with the model zoo import-blocked serves the artifact:
    params come from params.npz, the graph from apply.export — nothing rebuilds
    or traces the model. The TPU analogue of serving the reference's exported
    GraphDef in vanilla TF (test_saved_model.py:26-40)."""
    import subprocess
    import sys

    from autodist_tpu.models import transformer_lm

    cfg = transformer_lm.TransformerLMConfig(
        vocab_size=89, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_len=16,
        dtype=jnp.float32)
    model, params = transformer_lm.init_params(cfg)
    toks = np.random.RandomState(0).randint(0, 89, (3, 8)).astype(np.int32)

    def apply_fn(p, tokens):
        return model.apply({"params": p}, tokens)

    export_dir = str(tmp_path / "serve_lm")
    SavedModelBuilder(export_dir).save(
        params, model_config={"family": "transformer_lm"},
        apply_fn=apply_fn, example_args=(toks,))
    expected = np.asarray(apply_fn(params, jnp.asarray(toks)))
    np.save(str(tmp_path / "tokens.npy"), toks)
    np.save(str(tmp_path / "expected.npy"), expected)

    driver = f"""
import sys
# Serving must not need the model zoo: make importing it a hard failure.
sys.modules["autodist_tpu.models"] = None
sys.modules["autodist_tpu.models.transformer_lm"] = None
# Pin the child to CPU: tests run on the virtual CPU mesh, and expected.npy
# was computed there.
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from autodist_tpu.checkpoint.saved_model_builder import SavedModelBuilder
params = SavedModelBuilder.load_params({export_dir!r})
serve = SavedModelBuilder.load_serving_fn({export_dir!r})
out = np.asarray(serve(params, np.load({str(tmp_path / "tokens.npy")!r})))
np.testing.assert_allclose(out, np.load({str(tmp_path / "expected.npy")!r}),
                           rtol=1e-5, atol=1e-5)
print("SERVED_OK", out.shape)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(os.path.dirname(__file__)))
    proc = subprocess.run([sys.executable, "-c", driver], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert "SERVED_OK" in proc.stdout


def test_saved_model_polymorphic_batch(tmp_path):
    """polymorphic_batch=True bakes a symbolic leading dim: one artifact serves
    any batch size. Scalar example args stay concrete (no rank promotion)."""
    params = _params()

    def apply_fn(p, x, scale):
        return (x @ p["dense"]["w"] + p["dense"]["b"]) * scale

    export_dir = str(tmp_path / "serve_poly")
    SavedModelBuilder(export_dir).save(
        params, apply_fn=apply_fn,
        example_args=(np.zeros((2, 16), np.float32), np.float32(2.0)),
        polymorphic_batch=True)
    serve = SavedModelBuilder.load_serving_fn(export_dir)
    loaded = SavedModelBuilder.load_params(export_dir)
    for batch in (1, 2, 7):
        x = np.asarray(np.random.RandomState(batch).randn(batch, 16), np.float32)
        np.testing.assert_allclose(np.asarray(serve(loaded, x, np.float32(2.0))),
                                   np.asarray(apply_fn(params, x, 2.0)),
                                   rtol=1e-6, atol=1e-6)


def test_ef_restore_across_dp_topologies(tmp_path):
    """Checkpoints with per-replica compressor residuals restore onto a different
    data-parallel size: shape-stable leaves (PowerSGD Q) restore, dp-sized residuals
    reinitialize to zeros instead of hard-failing."""
    from autodist_tpu.parallel.mesh import build_mesh
    from autodist_tpu.parallel.plan import ShardingPlan
    from autodist_tpu.model_spec import ModelSpec
    from autodist_tpu.runner import DistributedRunner

    params, batch = _params(), _batch()
    builder = AllReduce(compressor="PowerSGDCompressor", power_sgd_rank=2)
    runner_a, state_a = _train(builder, 3, params, batch)
    saver = Saver()
    prefix = saver.save(state_a, str(tmp_path / "ckpt"))

    # Same strategy, but a 4-device mesh (dp=4 instead of 8).
    spec_model = ModelSpec(params)
    strategy = builder.build(spec_model, AutoDist().resource_spec)
    plan = ShardingPlan.from_strategy(strategy, spec_model)
    mesh_b = build_mesh(axes={"data": 4}, devices=jax.devices()[:4])
    runner_b = DistributedRunner(strategy, spec_model, _loss, optax.adam(1e-2),
                                 mesh=mesh_b, plan=plan)
    state_b = saver.restore(prefix, runner=runner_b)
    np.testing.assert_allclose(np.asarray(state_b.params["dense"]["w"]),
                               np.asarray(jax.device_get(state_a.params["dense"]["w"])),
                               rtol=1e-6)
    # Q is topology-independent: restored. Residual reinitialized at dp=4.
    np.testing.assert_allclose(np.asarray(state_b.ef_state["dense"]["w"].q),
                               np.asarray(jax.device_get(state_a.ef_state["dense"]["w"].q)),
                               rtol=1e-6)
    err_b = np.asarray(state_b.ef_state["dense"]["w"].error)
    assert err_b.shape[0] == 4
    assert np.all(err_b == 0)
    # And training continues.
    state_b2, loss = runner_b.run(state_b, batch)
    assert np.isfinite(float(loss))


def test_rotation_survives_restart(tmp_path):
    """A restarted trainer (fresh Saver) must keep rotating checkpoints written
    before the restart: rotation state persists in the 'checkpoint' state file."""
    import glob

    import numpy as np

    from autodist_tpu.checkpoint import Saver

    params = {"w": np.ones((2,), np.float32)}
    s1 = Saver(max_to_keep=2)
    for step in range(3):
        s1.save(params, str(tmp_path / "ck"), global_step=step)
    assert sorted(glob.glob(str(tmp_path / "ck-*.npz"))) == [
        str(tmp_path / "ck-1.npz"), str(tmp_path / "ck-2.npz")]

    s2 = Saver(max_to_keep=2)  # simulated restart
    s2.save(params, str(tmp_path / "ck"), global_step=3)
    assert sorted(glob.glob(str(tmp_path / "ck-*.npz"))) == [
        str(tmp_path / "ck-2.npz"), str(tmp_path / "ck-3.npz")]


def test_user_preserved_checkpoint_survives_restart_rotation(tmp_path):
    """A matching-name file the user copied into the directory to keep (never
    recorded in the rotation list) must not be rotate-deleted after a restart;
    only the recorded checkpoints rotate."""
    import glob
    import shutil

    import numpy as np

    from autodist_tpu.checkpoint import Saver

    params = {"w": np.ones((2,), np.float32)}
    s1 = Saver(max_to_keep=2)
    for step in range(3):
        s1.save(params, str(tmp_path / "ck"), global_step=step)
    # User deliberately preserves step 1 beyond rotation under the same pattern.
    shutil.copy(str(tmp_path / "ck-1.npz"), str(tmp_path / "ck-100.npz"))

    s2 = Saver(max_to_keep=2)  # restart: adopts only the RECORDED rotation list
    for step in (3, 4, 5):
        s2.save(params, str(tmp_path / "ck"), global_step=step)
    remaining = sorted(glob.glob(str(tmp_path / "ck-*.npz")))
    assert str(tmp_path / "ck-100.npz") in remaining
    assert remaining == [str(tmp_path / "ck-100.npz"),
                         str(tmp_path / "ck-4.npz"), str(tmp_path / "ck-5.npz")]


def test_sharded_format_roundtrip_and_rotation(tmp_path):
    """Forced sharded format in one process: manifest + shard files written,
    restore (with runner and to host numpy) is value-exact, rotation sweeps
    the per-shard files, and latest_checkpoint resolves manifest-only
    checkpoints."""
    import glob

    batch = _batch()
    runner, state = _train(PS(), 2, _params(), batch)
    saver = Saver(max_to_keep=2)
    for step in (2, 3, 4):
        prefix = saver.save(state, str(tmp_path / "ck"), global_step=step,
                            sharded=True)
    assert not [f for f in glob.glob(str(tmp_path / "ck-*.npz"))
                if ".shard" not in f]  # no monolithic files
    shard_files = glob.glob(str(tmp_path / "ck-*.shard*-of-*.npz"))
    assert {os.path.basename(f).split(".")[0] for f in shard_files} == \
        {"ck-3", "ck-4"}  # ck-2 rotated away, shards swept with it
    assert Saver.latest_checkpoint(str(tmp_path), name="ck") == \
        str(tmp_path / "ck-4")

    state_b = Saver().restore(str(tmp_path / "ck-4"), runner=runner)
    np.testing.assert_allclose(
        np.asarray(jax.device_get(state_b.params["dense"]["w"])),
        np.asarray(jax.device_get(state.params["dense"]["w"])), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(state.opt_state),
                    jax.tree_util.tree_leaves(state_b.opt_state)):
        np.testing.assert_allclose(np.asarray(jax.device_get(a)),
                                   np.asarray(jax.device_get(b)), rtol=1e-6)
    host = Saver().restore_params(str(tmp_path / "ck-4"))
    assert host["dense"]["w"].shape == (16, 4)


def test_sharded_format_bf16_leaves(tmp_path):
    """bfloat16 leaves round-trip through the sharded format (stored as
    same-width uints, true dtype recorded in the manifest)."""
    params = {"w": jnp.asarray(np.random.RandomState(0).randn(8, 4),
                               jnp.bfloat16),
              "b": jnp.zeros((4,), jnp.float32)}
    prefix = Saver().save(params, str(tmp_path / "bf"), sharded=True)
    loaded = Saver().restore_params(prefix)
    assert loaded["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(loaded["w"], np.float32),
        np.asarray(jax.device_get(params["w"]), np.float32))


def test_async_save_double_buffered(tmp_path):
    """async_write snapshots synchronously and writes in the background; a
    following save joins the previous write, wait() surfaces the result, and
    the files are complete and loadable afterwards."""
    runner, state = _train(PS(), 1, _params(), _batch())
    saver = Saver(max_to_keep=5)
    for step in (1, 2):
        saver.save(state, str(tmp_path / "as"), global_step=step,
                   async_write=True)
    saver.wait()
    assert os.path.exists(str(tmp_path / "as-1.npz"))
    latest = Saver.latest_checkpoint(str(tmp_path), name="as")
    assert latest == str(tmp_path / "as-2")
    restored = Saver().restore(latest, runner=runner)
    np.testing.assert_allclose(
        np.asarray(jax.device_get(restored.params["dense"]["w"])),
        np.asarray(jax.device_get(state.params["dense"]["w"])), rtol=1e-6)


def test_async_save_failure_surfaces_on_wait(tmp_path, monkeypatch):
    """A background write that dies re-raises from wait() (and from the next
    save), not silently."""
    saver = Saver()
    target = tmp_path / "x"
    saver.save({"w": jnp.zeros((2,))}, str(target), async_write=True)
    saver.wait()

    def boom(*a, **k):
        raise OSError("disk full")

    monkeypatch.setattr(np, "savez", boom)
    saver.save({"w": jnp.zeros((2,))}, str(target), global_step=7,
               async_write=True)
    with pytest.raises(RuntimeError, match="async checkpoint write"):
        saver.wait()


def test_fresh_directory_without_state_file_still_adopts(tmp_path):
    """No state file (e.g. deleted, or checkpoints rsynced in): fall back to
    adopting the on-disk scan so rotation still bounds disk use."""
    import glob
    import os

    import numpy as np

    from autodist_tpu.checkpoint import Saver

    params = {"w": np.ones((2,), np.float32)}
    s1 = Saver(max_to_keep=2)
    for step in range(3):
        s1.save(params, str(tmp_path / "ck"), global_step=step)
    os.remove(str(tmp_path / "checkpoint"))

    s2 = Saver(max_to_keep=2)
    s2.save(params, str(tmp_path / "ck"), global_step=3)
    assert sorted(glob.glob(str(tmp_path / "ck-*.npz"))) == [
        str(tmp_path / "ck-2.npz"), str(tmp_path / "ck-3.npz")]
