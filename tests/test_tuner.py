"""Empirical strategy tuner: measures candidates, ranks, survives failures."""

import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu.model_spec import ModelSpec
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy import (AllReduce, PSLoadBalancing, Strategy,
                                   StrategyBuilder, TuneResult, tune_strategy)


def _loss(p, b):
    return jnp.mean((b["y"] - (b["x"] @ p["w"] + p["b"])) ** 2)


def _params():
    rng = np.random.RandomState(0)
    return {"w": rng.randn(4, 1).astype(np.float32), "b": np.zeros((1,), np.float32)}


def _batch():
    rng = np.random.RandomState(1)
    return {"x": rng.randn(32, 4).astype(np.float32),
            "y": rng.randn(32, 1).astype(np.float32)}


class ExplodingBuilder(StrategyBuilder):
    def build(self, model_spec: ModelSpec, resource_spec: ResourceSpec) -> Strategy:
        raise RuntimeError("boom")


def test_tuner_ranks_candidates():
    result = tune_strategy(_loss, _params(), optax.sgd(0.1), _batch(),
                           candidates=[AllReduce(), PSLoadBalancing()],
                           warmup_steps=1, measure_steps=3)
    assert isinstance(result, TuneResult)
    assert len(result.results) == 2
    assert all(r.steps_per_sec and r.steps_per_sec > 0 for r in result.results)
    assert result.best in [r.builder for r in result.results]
    report = result.report()
    assert "AllReduce" in report and "PSLoadBalancing" in report
    assert "<- best" in report


def test_tuner_skips_failing_candidate():
    result = tune_strategy(_loss, _params(), optax.sgd(0.1), _batch(),
                           candidates=[ExplodingBuilder(), AllReduce()],
                           warmup_steps=1, measure_steps=2)
    failed = [r for r in result.results if r.steps_per_sec is None]
    assert len(failed) == 1 and "boom" in failed[0].error
    assert type(result.best).__name__ == "AllReduce"
    assert "FAILED" in result.report()


def test_tuner_all_failing_raises():
    with pytest.raises(RuntimeError, match="every candidate failed"):
        tune_strategy(_loss, _params(), optax.sgd(0.1), _batch(),
                      candidates=[ExplodingBuilder()])


def test_tuner_with_accumulation():
    result = tune_strategy(_loss, _params(), optax.sgd(0.1), _batch(),
                           candidates=[AllReduce()], warmup_steps=1,
                           measure_steps=2, accumulation_steps=2)
    assert result.results[0].steps_per_sec > 0


def test_tuner_with_aux_loss():
    def loss_aux(p, b):
        err = b["y"] - (b["x"] @ p["w"] + p["b"])
        return jnp.mean(err ** 2), {"mae": jnp.mean(jnp.abs(err))}

    result = tune_strategy(loss_aux, _params(), optax.sgd(0.1), _batch(),
                           candidates=[AllReduce(), PSLoadBalancing()],
                           warmup_steps=1, measure_steps=2, has_aux=True)
    assert all(r.steps_per_sec for r in result.results)


def test_tuner_restores_default_autodist():
    from autodist_tpu import AutoDist, get_default_autodist
    mine = AutoDist(strategy_builder=AllReduce())
    tune_strategy(_loss, _params(), optax.sgd(0.1), _batch(),
                  candidates=[PSLoadBalancing()], warmup_steps=1, measure_steps=2)
    assert get_default_autodist() is mine


def test_tuner_rejects_multinode_spec():
    """Ranking is sync-local: a multi-node spec must be rejected up front, not
    silently measured on local devices."""
    spec = ResourceSpec(
        "nodes: [{address: 10.0.0.1, tpus: 4, chief: true}, "
        "{address: 10.0.0.2, tpus: 4}]")
    with pytest.raises(ValueError, match="multi-node"):
        tune_strategy(_loss, _params(), optax.sgd(0.1), _batch(),
                      candidates=[AllReduce()], resource_spec=spec)


def test_tuner_skips_async_candidate():
    """An async candidate is recorded as skipped (gate-dominated wall-clock is
    not comparable to a sync step), and a sync candidate still wins."""
    from autodist_tpu.strategy import PS
    result = tune_strategy(_loss, _params(), optax.sgd(0.1), _batch(),
                           candidates=[PS(sync=False), AllReduce()],
                           warmup_steps=1, measure_steps=2)
    skipped = [r for r in result.results if r.steps_per_sec is None]
    assert len(skipped) == 1 and "async" in skipped[0].error
    assert type(result.best).__name__ == "AllReduce"


def test_tuner_sweeps_accumulation_steps():
    result = tune_strategy(_loss, _params(), optax.sgd(0.1), _batch(),
                           candidates=[AllReduce()], warmup_steps=1,
                           measure_steps=2, accumulation_steps=[1, 2])
    names = {r.name for r in result.results}
    assert names == {"AllReduce[accum=1]", "AllReduce[accum=2]"}
    assert result.best_accumulation_steps in (1, 2)
    assert "<- best" in result.report()


def test_tuner_rejects_zero_warmup():
    with pytest.raises(ValueError, match="warmup_steps"):
        tune_strategy(_loss, _params(), optax.sgd(0.1), _batch(),
                      candidates=[AllReduce()], warmup_steps=0)


def test_tuner_default_candidates_include_parallax_for_sparse():
    rng = np.random.RandomState(2)
    params = {"emb": rng.randn(50, 4).astype(np.float32),
              "w": rng.randn(4, 1).astype(np.float32)}
    batch = {"idx": rng.randint(0, 50, (32,)),
             "y": rng.randn(32, 1).astype(np.float32)}

    def loss(p, b):
        return jnp.mean((b["y"] - jnp.take(p["emb"], b["idx"], axis=0) @ p["w"]) ** 2)

    result = tune_strategy(loss, params, optax.sgd(0.1), batch,
                           warmup_steps=1, measure_steps=2)
    names = {r.name for r in result.results}
    assert "Parallax" in names and "AllReduce" in names and "AutoStrategy" in names
