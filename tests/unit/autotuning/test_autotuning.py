"""Autotuner suite — analog of reference ``tests/unit/autotuning/``."""

import json
import os

import numpy as np
import pytest

from deepspeed_tpu.autotuning import (
    Autotuner,
    CostModel,
    GridSearchTuner,
    ModelBasedTuner,
    RandomTuner,
)


def _exps(n=6):
    return [{"name": f"e{i}",
             "ds_config": {"train_micro_batch_size_per_gpu": 2 ** i,
                           "zero_optimization": {"stage": i % 4}}}
            for i in range(n)]


class TestTuners:
    def test_gridsearch_finds_best(self):
        scores = {f"e{i}": float(i) for i in range(6)}
        t = GridSearchTuner(_exps(), lambda e: scores[e["name"]],
                            early_stopping=10)
        best, metric = t.tune()
        assert best["name"] == "e5" and metric == 5.0

    def test_early_stopping(self):
        calls = []

        def metric(e):
            calls.append(e["name"])
            return 10.0 if e["name"] == "e0" else 0.0

        t = GridSearchTuner(_exps(), metric, early_stopping=2)
        best, _ = t.tune()
        assert best["name"] == "e0"
        assert len(calls) == 3  # e0 + 2 stale

    def test_random_tuner_deterministic_seed(self):
        scores = {f"e{i}": float(i) for i in range(6)}
        t1 = RandomTuner(_exps(), lambda e: scores[e["name"]],
                         early_stopping=10, seed=3)
        t2 = RandomTuner(_exps(), lambda e: scores[e["name"]],
                         early_stopping=10, seed=3)
        b1, _ = t1.tune()
        b2, _ = t2.tune()
        assert b1["name"] == b2["name"] == "e5"
        # same seed → same visit order
        assert [r[0]["name"] for r in t1.records] == \
            [r[0]["name"] for r in t2.records]

    def test_model_based_tuner(self):
        # metric peaked at mbs=8 → surrogate should still find the max
        def metric(e):
            mbs = e["ds_config"]["train_micro_batch_size_per_gpu"]
            return -abs(mbs - 8)

        t = ModelBasedTuner(_exps(), metric, early_stopping=10,
                            seed_trials=3)
        best, m = t.tune()
        assert best["ds_config"]["train_micro_batch_size_per_gpu"] == 8

    def test_cost_model_boosted_trees_fit_quadratic(self):
        cm = CostModel()
        X = [[float(i), 1.0, 0.0] for i in range(8)]
        y = [-(i - 4.0) ** 2 for i in range(8)]
        cm.fit(X, y)
        assert cm._trees, "8 samples must take the boosted-tree path"
        preds = [cm.predict([float(i), 1.0, 0.0]) for i in range(8)]
        assert int(np.argmax(preds)) == 4

    def test_cost_model_flat_metrics_predict_the_mean(self):
        cm = CostModel()
        X = [[float(i), 1.0, 0.0] for i in range(8)]
        cm.fit(X, [5.0] * 8)  # zero-residual: no trees grown
        assert cm._boosted and not cm._trees
        assert abs(cm.predict([3.0, 1.0, 0.0]) - 5.0) < 1e-9

    def test_cost_model_boosted_trees_fit_nonsmooth_interaction(self):
        """The GBDT surrogate must rank a cliff + interaction surface a
        quadratic cannot represent (e.g. OOM cliff at mbs>8 composed with
        a zero-stage interaction)."""
        grid = [(float(m), float(s)) for m in range(1, 13) for s in (0., 2.)]

        def truth(m, s):
            if m > 8:            # OOM cliff
                return -100.0
            return m * (2.0 if s == 2.0 else 1.0)  # stage-2 doubles gain

        X = [[m, 1.0, s] for m, s in grid]
        y = [truth(m, s) for m, s in grid]
        cm = CostModel()
        cm.fit(X, y)
        preds = {(m, s): cm.predict([m, 1.0, s]) for m, s in grid}
        best = max(preds, key=preds.get)
        assert best == (8.0, 2.0), best
        # the cliff must be learned: any mbs>8 predicts far below the best
        assert all(preds[(m, s)] < preds[(8.0, 2.0)] - 50
                   for m, s in grid if m > 8)
        assert cm._trees, "expected the boosted-tree path, not the fallback"

    def test_cost_model_quadratic_fallback_small_sample(self):
        cm = CostModel()
        X = [[float(i), 1.0, 0.0] for i in range(4)]  # < min_tree_samples
        cm.fit(X, [float(2 * i) for i in range(4)])
        assert not cm._trees and cm._w is not None
        assert abs(cm.predict([5.0, 1.0, 0.0]) - 10.0) < 1e-6


class TestAutotunerInProcess:
    def _factories(self):
        from tests.unit.simple_model import SimpleModel

        def model_factory():
            return SimpleModel(hidden_dim=16)

        def batch_factory(batch_size):
            rng = np.random.default_rng(0)
            return {"x": rng.standard_normal((batch_size, 16),
                                             dtype=np.float32),
                    "y": rng.standard_normal((batch_size,),
                                             dtype=np.float32)}

        return model_factory, batch_factory

    def test_generate_experiments_grid(self):
        mf, bf = self._factories()
        at = Autotuner(mf, bf,
                       base_config={"optimizer": {"type": "Adam",
                                                  "params": {"lr": 1e-3}}},
                       autotuning_config={
                           "num_tuning_micro_batch_sizes": 2,
                           "max_train_micro_batch_size_per_gpu": 4})
        exps = at._generate_experiments()
        assert len(exps) == 4 * 2
        stages = {e["ds_config"]["zero_optimization"]["stage"] for e in exps}
        assert stages == {0, 1, 2, 3}

    def test_model_info(self):
        mf, bf = self._factories()
        at = Autotuner(mf, bf)
        info = at.model_info()
        assert info["num_params"] > 0
        assert info["param_mem_per_stage"][3] < \
            info["param_mem_per_stage"][0]

    def test_tune_end_to_end(self, tmp_path):
        mf, bf = self._factories()
        at = Autotuner(
            mf, bf,
            base_config={"optimizer": {"type": "Adam",
                                       "params": {"lr": 1e-3}},
                         "steps_per_print": 1000},
            autotuning_config={
                "num_tuning_micro_batch_sizes": 2,
                "max_train_micro_batch_size_per_gpu": 8,
                "start_profile_step": 1, "end_profile_step": 3,
                "results_dir": str(tmp_path / "results")})
        best = at.tune(stages=[0, 1])
        assert best and "ds_config" in best
        assert os.path.exists(tmp_path / "results" /
                              "autotuning_results.json")
        assert os.path.exists(tmp_path / "results" / "best_config.json")
        with open(tmp_path / "results" / "best_config.json") as f:
            cfg = json.load(f)
        assert "train_micro_batch_size_per_gpu" in cfg


def test_engine_writes_metric_file(tmp_path):
    import deepspeed_tpu as ds
    from tests.unit.simple_model import SimpleModel, random_batch

    metric_path = str(tmp_path / "metric.json")
    config = {
        "train_micro_batch_size_per_gpu": 4,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "autotuning": {"enabled": True, "metric_path": metric_path,
                       "start_profile_step": 1, "end_profile_step": 3},
        "steps_per_print": 1000,
    }
    engine, _, _, _ = ds.initialize(model=SimpleModel(hidden_dim=16),
                                    config=config)
    b = random_batch(engine.train_batch_size())
    for _ in range(4):
        engine.train_batch(batch=b)
    with open(metric_path) as f:
        m = json.load(f)
    assert m["throughput"] > 0
    assert m["steps"] == 2


# ---------------------------------------------------------------------------
# round 2: ResourceManager — real subprocess experiments, measured metrics
# ---------------------------------------------------------------------------
TOY_SCRIPT = '''
import os, numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import flax.linen as nn
import deepspeed_tpu as ds


class Toy(nn.Module):
    @nn.compact
    def __call__(self, batch):
        x = batch["x"]
        y = nn.Dense(16)(jax.nn.relu(nn.Dense(16)(x)))
        return jnp.mean((y - batch["y"]) ** 2)


# config comes from DS_AUTOTUNING_CONFIG (engine reads the env override)
engine, _, _, _ = ds.initialize(model=Toy(), config={
    "train_micro_batch_size_per_gpu": 1,
    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}})
rng = np.random.default_rng(0)
batch = {"x": rng.standard_normal((engine.train_batch_size(), 8)).astype("float32"),
         "y": rng.standard_normal((engine.train_batch_size(), 16)).astype("float32")}
for _ in range(64):  # DS_AUTOTUNING_EXIT ends the run after the window
    engine.train_batch(batch=batch)
'''


class TestResourceManager:
    def test_node_reservations(self):
        from deepspeed_tpu.autotuning import Node

        n = Node("h1", 2)
        a = n.reserve(1)
        b = n.reserve(1)
        assert a == [0] and b == [1]
        assert n.reserve(1) is None
        n.release(a)
        assert n.reserve(1) == [0]

    def test_end_to_end_real_experiments(self, tmp_path):
        """The done-criterion: an end-to-end tune over a toy model with
        REAL measured metrics — each experiment is a subprocess run of the
        user script; throughput comes from the engine's profile window."""
        from deepspeed_tpu.autotuning import ResourceManager

        script = tmp_path / "train_toy.py"
        script.write_text(TOY_SCRIPT)
        exps = []
        for stage in (0, 1):
            exps.append({
                "name": f"z{stage}",
                "ds_config": {
                    "train_micro_batch_size_per_gpu": 2,
                    "zero_optimization": {"stage": stage},
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                    "autotuning": {"enabled": True,
                                   "start_profile_step": 2,
                                   "end_profile_step": 4},
                },
            })
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
        manager = ResourceManager(
            hosts={"localhost": 1},
            results_dir=str(tmp_path / "results"),
            exps_dir=str(tmp_path / "exps"),
            env={"JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": repo_root + os.pathsep +
                 os.environ.get("PYTHONPATH", "")})
        manager.schedule_experiments(exps)
        finished = manager.run(str(script), [])
        assert len(finished) == 2
        for exp in finished.values():
            assert exp["returncode"] == 0, \
                open(os.path.join(exp["result_dir"], "stderr.log")).read()[-2000:]
            assert exp["metrics"] is not None
            assert exp["metrics"]["throughput"] > 0
            assert exp["metrics"]["steps"] == 2
        best = manager.best("throughput")
        assert best is not None
        assert best["name"] in ("z0", "z1")
        assert "autotuning" not in best["ds_config"]

        # resume: re-scheduling the same experiments skips both runs
        m2 = ResourceManager(
            hosts={"localhost": 1},
            results_dir=str(tmp_path / "results"),
            exps_dir=str(tmp_path / "exps"))
        m2.schedule_experiments(exps)
        assert not m2.experiment_queue
        assert len(m2.finished) == 2

    def test_arg_mappings_rewrite(self, tmp_path):
        from deepspeed_tpu.autotuning.scheduler import _get_by_dotted_key

        cfg = {"train_micro_batch_size_per_gpu": 4,
               "zero_optimization": {"stage": 2}}
        assert _get_by_dotted_key(cfg, "train_micro_batch_size_per_gpu") == 4
        assert _get_by_dotted_key(cfg, "zero_optimization.stage") == 2
        assert _get_by_dotted_key(cfg, "zero_optimization.missing") is None
