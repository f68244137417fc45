"""train(): checkpoint/resume loop — an interrupted run continues exactly.

Mirrors the reference's resumability contract (chief-gated saver on a shared
filesystem, ``tests/integration/cases/c10.py``) at the API level: a run killed
after a save and restarted with the same command must land on the same final
state as the uninterrupted run.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import AutoDist, train
from autodist_tpu.checkpoint.saver import Saver
from autodist_tpu.strategy import AllReduce


def _loss(p, b):
    return jnp.mean((b["y"] - (b["x"] @ p["w"] + p["b"])) ** 2)


def _params():
    rng = np.random.RandomState(7)
    return {"w": rng.randn(4, 1).astype(np.float32), "b": np.zeros((1,), np.float32)}


def _batch_fn(i):
    rng = np.random.RandomState(100 + i)   # deterministic per-step batches
    return {"x": rng.randn(32, 4).astype(np.float32),
            "y": rng.randn(32, 1).astype(np.float32)}


def _runner():
    ad = AutoDist(strategy_builder=AllReduce())
    return ad.create_distributed_session(_loss, _params(), optax.adam(1e-2),
                                         example_batch=_batch_fn(0))


def test_uninterrupted_vs_resumed_identical(tmp_path):
    direct = train(_runner(), _params(), _batch_fn, steps=10, log_every=0)

    ckpt = str(tmp_path / "ckpts")
    first = train(_runner(), _params(), _batch_fn, steps=4, checkpoint_dir=ckpt,
                  log_every=0)
    assert int(first.step) == 4
    assert Saver.latest_checkpoint(ckpt) is not None

    resumed = train(_runner(), _params(), _batch_fn, steps=10,
                    checkpoint_dir=ckpt, log_every=0)
    assert int(resumed.step) == 10
    d, r = jax.device_get(direct.params), jax.device_get(resumed.params)
    for k in d:
        np.testing.assert_allclose(r[k], d[k], rtol=1e-6, atol=1e-6)


def test_resume_skips_completed_run(tmp_path):
    ckpt = str(tmp_path / "ckpts")
    done = train(_runner(), _params(), _batch_fn, steps=5, checkpoint_dir=ckpt,
                 log_every=0)
    again = train(_runner(), _params(), _batch_fn, steps=5, checkpoint_dir=ckpt,
                  log_every=0)
    assert int(again.step) == 5
    d, a = jax.device_get(done.params), jax.device_get(again.params)
    for k in d:
        np.testing.assert_allclose(a[k], d[k], rtol=1e-6, atol=1e-6)


def test_periodic_saves_and_rotation(tmp_path):
    ckpt = str(tmp_path / "ckpts")
    train(_runner(), _params(), _batch_fn, steps=9, checkpoint_dir=ckpt,
          save_every=2, max_to_keep=3, log_every=0)
    import glob
    kept = sorted(glob.glob(f"{ckpt}/model-*.npz"))
    assert len(kept) == 3  # rotation caps the kept set
    assert Saver.latest_checkpoint(ckpt).endswith("model-9")


def test_async_periodic_saves_match_sync(tmp_path):
    """async_save=True: periodic writes ride the background thread but the
    on-disk result — rotation, latest pointer, resumability — is identical
    to synchronous saving, and train() returns with everything durable."""
    sync_dir, async_dir = str(tmp_path / "sync"), str(tmp_path / "async")
    train(_runner(), _params(), _batch_fn, steps=9, checkpoint_dir=sync_dir,
          save_every=2, max_to_keep=3, log_every=0)
    train(_runner(), _params(), _batch_fn, steps=9, checkpoint_dir=async_dir,
          save_every=2, max_to_keep=3, log_every=0, async_save=True)
    import glob
    import os
    names = lambda d: sorted(os.path.basename(p)  # noqa: E731
                             for p in glob.glob(f"{d}/model-*.npz"))
    assert names(sync_dir) == names(async_dir)
    assert Saver.latest_checkpoint(async_dir).endswith("model-9")
    resumed = train(_runner(), _params(), _batch_fn, steps=12,
                    checkpoint_dir=async_dir, log_every=0, async_save=True)
    direct = train(_runner(), _params(), _batch_fn, steps=12, log_every=0)
    d, r = jax.device_get(direct.params), jax.device_get(resumed.params)
    for k in d:
        np.testing.assert_allclose(r[k], d[k], rtol=1e-6, atol=1e-6)


def test_iterator_batches_end_early():
    batches = [_batch_fn(i) for i in range(4)]
    state = train(_runner(), _params(), iter(batches), steps=100, log_every=0)
    assert int(state.step) == 4


def test_iterator_resume_fast_forwards(tmp_path):
    """Resumed iterable runs must not replay already-consumed batches."""
    direct = train(_runner(), _params(), [_batch_fn(i) for i in range(8)],
                   steps=8, log_every=0)
    ckpt = str(tmp_path / "ckpts")
    train(_runner(), _params(), [_batch_fn(i) for i in range(8)], steps=4,
          checkpoint_dir=ckpt, log_every=0)
    resumed = train(_runner(), _params(), [_batch_fn(i) for i in range(8)],
                    steps=8, checkpoint_dir=ckpt, log_every=0)
    assert int(resumed.step) == 8
    d, r = jax.device_get(direct.params), jax.device_get(resumed.params)
    for k in d:
        np.testing.assert_allclose(r[k], d[k], rtol=1e-6, atol=1e-6)


def test_two_names_share_directory_without_cross_talk(tmp_path):
    """GAN-style: two models checkpoint into one directory under different
    names; each resumes its own line and never rotates the other's files."""
    ckpt = str(tmp_path / "ckpts")
    a = train(_runner(), _params(), _batch_fn, steps=3, checkpoint_dir=ckpt,
              checkpoint_name="gen", log_every=0)
    b = train(_runner(), _params(), _batch_fn, steps=5, checkpoint_dir=ckpt,
              checkpoint_name="disc", save_every=2, max_to_keep=2, log_every=0)
    # Resume "gen" to 6: must restore gen-3 (not disc-5) and extend it.
    a2 = train(_runner(), _params(), _batch_fn, steps=6, checkpoint_dir=ckpt,
               checkpoint_name="gen", log_every=0)
    assert int(a2.step) == 6
    direct = train(_runner(), _params(), _batch_fn, steps=6, log_every=0)
    d, r = jax.device_get(direct.params), jax.device_get(a2.params)
    for k in d:
        np.testing.assert_allclose(r[k], d[k], rtol=1e-6, atol=1e-6)
    import glob
    # disc's rotation (max_to_keep=2) never deleted gen's files.
    assert sorted(p.split("/")[-1] for p in glob.glob(f"{ckpt}/gen-*.npz")) \
        == ["gen-3.npz", "gen-6.npz"]
    assert len(glob.glob(f"{ckpt}/disc-*.npz")) == 2


def test_dash_prefix_names_do_not_collide(tmp_path):
    """name="gen" must never resume from "gen-ema" checkpoints."""
    ckpt = str(tmp_path / "ckpts")
    train(_runner(), _params(), _batch_fn, steps=3, checkpoint_dir=ckpt,
          checkpoint_name="gen", log_every=0)
    train(_runner(), _params(), _batch_fn, steps=7, checkpoint_dir=ckpt,
          checkpoint_name="gen-ema", log_every=0)  # saves last -> owns state file
    assert Saver.latest_checkpoint(ckpt, name="gen").endswith("/gen-3")
    assert Saver.latest_checkpoint(ckpt, name="gen-ema").endswith("/gen-ema-7")
    resumed = train(_runner(), _params(), _batch_fn, steps=5, checkpoint_dir=ckpt,
                    checkpoint_name="gen", log_every=0)
    assert int(resumed.step) == 5  # resumed gen-3, not gen-ema-7


def test_eval_hook_fires_on_current_params(tmp_path):
    """eval_every runs the forward-only evaluate on the training state; the
    held-out loss decreases as training progresses."""
    evals = []
    held_out = _batch_fn(999)
    train(_runner(), _params(), _batch_fn, steps=9, log_every=0,
          eval_every=3, eval_batch=held_out,
          on_eval=lambda step, val: evals.append((step, float(val))))
    assert [s for s, _ in evals] == [3, 6, 9]
    assert evals[-1][1] < evals[0][1]


@pytest.mark.parametrize("unroll", [1, 2], ids=["per_step", "unrolled"])
def test_remote_worker_skips_eval(unroll):
    """A runner that says it is a remote async worker (its local state is a
    compile-shapes template) is never asked to evaluate, and is fed one step
    a dispatch whatever ``unroll`` asks."""
    runner = _runner()
    runner._is_remote_worker = True
    evals = []
    state = train(runner, _params(), _batch_fn, steps=4, log_every=0,
                  unroll=unroll, eval_every=2, eval_batch=_batch_fn(999),
                  on_eval=lambda step, val: evals.append(step))
    assert int(state.step) == 4 and evals == []


def test_eval_every_without_batch_raises():
    with pytest.raises(ValueError, match="eval_batch"):
        train(_runner(), _params(), _batch_fn, steps=2, eval_every=1)


def test_train_consumes_dataloader():
    """The native/fallback DataLoader's iterator plugs into train() directly
    (the host data pipeline and the loop compose)."""
    from autodist_tpu.data.loader import DataLoader
    rng = np.random.RandomState(5)
    loader = DataLoader({"x": rng.randn(96, 4).astype(np.float32),
                         "y": rng.randn(96, 1).astype(np.float32)},
                        batch_size=32)
    try:
        state = train(_runner(), _params(), iter(loader), steps=6, log_every=0)
        assert int(state.step) == 6  # continuous stream: never exhausts
    finally:
        loader.close()


def test_metrics_callback_fires():
    seen = []
    train(_runner(), _params(), _batch_fn, steps=7, log_every=3,
          on_metrics=lambda step, loss, rate: seen.append((step, loss, rate)))
    # The meter's first step is warmup (excluded), so periods end at 1+3k.
    assert [s for s, _, _ in seen] == [4, 7]
    assert all(rate > 0 for _, _, rate in seen)
