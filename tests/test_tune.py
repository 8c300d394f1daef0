"""Tune: search spaces, controller, schedulers (ASHA/PBT), function and
class trainables. Modeled on python/ray/tune/tests."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu import tune
from ray_tpu.tune import (ASHAScheduler, BasicVariantGenerator,
                          ConcurrencyLimiter, HyperBandScheduler,
                          MedianStoppingRule, PopulationBasedTraining,
                          Trainable, TuneConfig, Tuner)
from ray_tpu.tune.search.variant_generator import generate_variants


# -- search-space resolution (no cluster needed) ---------------------------

def test_grid_and_sample_resolution():
    space = {
        "lr": tune.grid_search([0.1, 0.01]),
        "wd": tune.uniform(0.0, 1.0),
        "depth": tune.grid_search([2, 4]),
        "nested": {"units": tune.choice([32, 64])},
    }
    variants = list(generate_variants(space, np.random.default_rng(0)))
    assert len(variants) == 4  # 2 x 2 grid
    assert {v["lr"] for v in variants} == {0.1, 0.01}
    for v in variants:
        assert 0.0 <= v["wd"] < 1.0
        assert v["nested"]["units"] in (32, 64)


def test_sample_from_sees_spec():
    space = {
        "a": tune.grid_search([3, 5]),
        "b": tune.sample_from(lambda spec: spec.config.a * 10),
    }
    variants = list(generate_variants(space, np.random.default_rng(0)))
    assert sorted(v["b"] for v in variants) == [30, 50]


def test_loguniform_and_randint_bounds():
    rng = np.random.default_rng(0)
    for _ in range(100):
        assert 1e-5 <= tune.loguniform(1e-5, 1e-1).sample(rng) <= 1e-1
        assert 2 <= tune.randint(2, 9).sample(rng) < 9
        assert tune.qrandint(0, 100, 10).sample(rng) % 10 == 0


# -- end-to-end experiments ------------------------------------------------

def _objective(config):
    score = 0.0
    for step in range(5):
        score += config["lr"]
        tune.report({"score": score, "step": step})


def test_function_trainable_grid(ray_start):
    results = tune.run(_objective,
                       config={"lr": tune.grid_search([0.1, 0.5, 1.0])},
                       metric="score", mode="max")
    assert len(results) == 3
    best = results.get_best_result()
    assert best.config["lr"] == 1.0
    assert best.metrics["score"] == pytest.approx(5.0)


class _StepTrainable(Trainable):
    def setup(self, config):
        self.value = 0.0

    def step(self):
        self.value += self.config["delta"]
        return {"value": self.value}

    def save_checkpoint(self):
        return {"value": self.value}

    def load_checkpoint(self, state):
        self.value = state["value"]


def test_class_trainable_asha_stops_bad_trials(ray_start):
    tuner = Tuner(
        _StepTrainable,
        # Descending order: weak trials reach each rung after strong ones
        # have set the cutoff (async halving cuts on arrival).
        param_space={"delta": tune.grid_search([2.0, 1.0, 0.2, 0.1])},
        tune_config=TuneConfig(
            metric="value", mode="max", max_concurrent_trials=4,
            scheduler=ASHAScheduler(max_t=12, grace_period=2,
                                    reduction_factor=2)))
    results = tuner.fit()
    best = results.get_best_result()
    assert best.config["delta"] == 2.0
    # ASHA must have cut at least one weak trial before max_t
    iters = [r.metrics.get("training_iteration", 0) for r in results.results]
    assert min(iters) < 12 and max(iters) == 12


def test_median_stopping(ray_start):
    results = tune.run(
        _StepTrainable,
        config={"delta": tune.grid_search([0.01, 1.0, 1.1, 1.2])},
        metric="value", mode="max", stop={"training_iteration": 10},
        scheduler=MedianStoppingRule(grace_period=2,
                                     min_samples_required=2))
    by_delta = {r.config["delta"]: r for r in results.results}
    slow = by_delta[0.01].metrics.get("training_iteration", 99)
    fast = by_delta[1.2].metrics.get("training_iteration", 0)
    assert slow <= fast


def _capped_together(marks: str, population: int = 4, last: int = 7):
    """A `_StepTrainable` of which no trial is done before `population`
    actors have reached step `last` (each writes the step it finished to
    a file of its own under `marks`), so the population is still running
    when its members are scored. A perturbation needs two scored,
    unfinished trials: under load the controller served one trial's
    seven steps before another's actor was up, each trial in turn was
    the only one scored, and `num_perturbations` stayed 0. Nothing
    blocks: a trial that is ahead keeps stepping."""
    import os
    import uuid

    class Capped(_StepTrainable):
        def setup(self, config):
            super().setup(config)
            self.mark = os.path.join(marks, uuid.uuid4().hex)

        def step(self):
            result = super().step()
            with open(self.mark + ".new", "w") as f:
                f.write(str(self._iteration + 1))
            os.replace(self.mark + ".new", self.mark)
            reached = [int(open(os.path.join(marks, name)).read())
                       for name in os.listdir(marks)
                       if not name.endswith(".new")]
            result["done"] = sum(at >= last for at in reached) >= population
            return result

    return Capped


def test_pbt_exploits_and_perturbs(ray_start, tmp_path):
    scheduler = PopulationBasedTraining(
        metric="value", mode="max", perturbation_interval=2,
        hyperparam_mutations={"delta": tune.uniform(0.5, 3.0)}, seed=0)
    tuner = Tuner(
        _StepTrainable,
        param_space={"delta": tune.grid_search([0.01, 0.02, 2.0, 3.0])},
        tune_config=TuneConfig(metric="value", mode="max",
                               max_concurrent_trials=4,
                               scheduler=scheduler,
                               time_budget_s=60))
    # Cap experiment length with the trainable's done flag, set once the
    # whole population has run its steps
    tuner._trainable = _capped_together(str(tmp_path))
    results = tuner.fit()
    assert scheduler.num_perturbations >= 1
    best = results.get_best_result()
    assert best.metrics["value"] > 2.0


def test_concurrency_limiter(ray_start):
    searcher = ConcurrencyLimiter(
        BasicVariantGenerator({"lr": tune.uniform(0, 1)}, num_samples=5,
                              seed=1, metric="score", mode="max"),
        max_concurrent=2)
    results = tune.run(_objective, search_alg=searcher, metric="score",
                       mode="max", max_concurrent_trials=4)
    assert len(results) == 5


def test_trial_error_surfaces(ray_start):
    def bad(config):
        raise ValueError("boom")

    results = tune.run(bad, config={}, metric="x", mode="max")
    assert len(results.errors) == 1
    assert "boom" in results.errors[0]


def test_hyperband_promotes(ray_start):
    results = tune.run(
        _StepTrainable,
        config={"delta": tune.grid_search([0.1, 0.5, 1.0, 2.0])},
        metric="value", mode="max",
        scheduler=HyperBandScheduler(max_t=9, reduction_factor=3))
    best = results.get_best_result()
    assert best.config["delta"] == 2.0


def test_pb2_gp_directed_explore(ray_start, tmp_path):
    """PB2 (reference: tune/schedulers/pb2.py): exploit configs come
    from the GP-UCB bandit within hyperparam_bounds, not random
    perturbation; the experiment still improves the population."""
    from ray_tpu.tune import PB2

    scheduler = PB2(
        metric="value", mode="max", perturbation_interval=2,
        hyperparam_bounds={"delta": (0.5, 3.0)}, seed=0)
    Capped = _capped_together(str(tmp_path))

    tuner = Tuner(
        Capped,
        param_space={"delta": tune.grid_search([0.01, 0.02, 2.0, 3.0])},
        tune_config=TuneConfig(metric="value", mode="max",
                               max_concurrent_trials=4,
                               scheduler=scheduler,
                               time_budget_s=60))
    results = tuner.fit()
    assert scheduler.num_perturbations >= 1
    # every exploited config stays inside the declared bounds
    for _, (_, cfg) in list(scheduler.pending_exploits.items()):
        assert 0.5 <= cfg["delta"] <= 3.0
    assert results.get_best_result().metrics["value"] > 2.0


def test_pb2_gp_math():
    """The internal GP interpolates a smooth function and UCB prefers
    the known-good region once data exists."""
    import numpy as np
    from ray_tpu.tune.schedulers.pb2 import _GP

    rng = np.random.default_rng(0)
    x = rng.uniform(size=(40, 2))
    y = np.sin(3 * x[:, 1]) + 0.01 * rng.normal(size=40)
    gp = _GP()
    gp.fit(x, (y - y.mean()) / y.std())
    q = np.array([[0.5, 0.5], [0.5, 0.52]])
    mu, sd = gp.predict(q)
    assert np.all(sd >= 0)
    # interpolation: prediction close to the true (normalized) function
    true = (np.sin(3 * q[:, 1]) - y.mean()) / y.std()
    assert np.all(np.abs(mu - true) < 0.35), (mu, true)
