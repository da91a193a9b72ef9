"""Advanced tune features: PBT, HyperBand, median stopping, TPE,
concurrency limiting, experiment resume.

Reference analogs: python/ray/tune/schedulers/{pbt,hyperband,
median_stopping_rule}.py, search/concurrency_limiter.py, and
execution/experiment_state.py (Tuner.restore).
"""

import json
import os
import shutil
import tempfile
import time

import pytest

import ray_tpu
from ray_tpu.train import RunConfig
from ray_tpu.tune import (
    ConcurrencyLimiter, HyperBandScheduler, MedianStoppingRule,
    PopulationBasedTraining, RandomSearcher, TPESearcher, TuneConfig,
    Tuner, grid_search, uniform,
)
from ray_tpu.tune.schedulers import CONTINUE, EXPLOIT, STOP


# ---------- scheduler units ----------

def test_median_stopping_rule():
    rule = MedianStoppingRule(metric="loss", mode="min",
                              grace_period=2, min_samples_required=3)
    # Four good trials descending, one bad plateauing high.
    for step in range(1, 5):
        for tid in ("a", "b", "c", "d"):
            assert rule.on_result(tid, {
                "loss": 1.0 / step, "training_iteration": step,
            }) == CONTINUE
    decisions = [rule.on_result("bad", {
        "loss": 10.0, "training_iteration": s}) for s in range(1, 4)]
    assert STOP in decisions


def test_hyperband_brackets_differ():
    hb = HyperBandScheduler(metric="loss", mode="min", max_t=27,
                            reduction_factor=3)
    assert len(hb._brackets) >= 2
    graces = {b.grace_period for b in hb._brackets}
    assert len(graces) >= 2          # distinct aggressiveness levels
    # Round-robin assignment spans brackets.
    hb.on_trial_add("t0", {})
    hb.on_trial_add("t1", {})
    assert hb._assignment["t0"] != hb._assignment["t1"]


def test_pbt_exploit_decision_and_mutation():
    pbt = PopulationBasedTraining(
        metric="score", mode="max", perturbation_interval=2,
        hyperparam_mutations={"lr": [0.1, 0.5, 1.0]}, seed=0)
    for i, tid in enumerate(("w", "x", "y", "z")):
        pbt.on_trial_add(tid, {"lr": 0.1 if i < 2 else 1.0})
        pbt.on_checkpoint(tid, f"/ckpt/{tid}")
    # Everyone reports at step 2; low scorers should exploit.
    assert pbt.on_result("y", {"score": 10, "training_iteration": 2}) \
        == CONTINUE
    assert pbt.on_result("z", {"score": 11, "training_iteration": 2}) \
        == CONTINUE
    assert pbt.on_result("x", {"score": 1, "training_iteration": 2}) \
        == EXPLOIT
    cfg, ckpt = pbt.exploit("x")
    assert ckpt in ("/ckpt/y", "/ckpt/z")
    assert cfg["lr"] in (0.1, 0.5, 1.0, 0.8, 1.2)  # mutated from donor


# ---------- searcher units ----------

def test_concurrency_limiter():
    base = RandomSearcher({"x": uniform(0, 1)}, num_samples=4, seed=0)
    lim = ConcurrencyLimiter(base, max_concurrent=2)
    a, b = lim.suggest("a"), lim.suggest("b")
    assert a is not None and b is not None
    assert lim.suggest("c") is None          # at capacity
    assert not lim.is_finished()
    lim.on_trial_complete("a", {"loss": 1.0})
    assert lim.suggest("c") is not None      # slot freed
    lim.on_trial_complete("b", {"loss": 1.0})
    assert lim.suggest("d") is not None
    lim.on_trial_complete("c", {"loss": 1.0})
    assert lim.suggest("e") is None
    assert lim.is_finished()


def test_tpe_concentrates_near_optimum():
    tpe = TPESearcher({"x": uniform(-5, 5)}, metric="loss",
                      mode="min", num_samples=40, n_startup=10, seed=3)
    suggested = []
    for i in range(40):
        tid = f"t{i}"
        cfg = tpe.suggest(tid)
        assert cfg is not None
        suggested.append(cfg["x"])
        tpe.on_trial_complete(tid, {"loss": (cfg["x"] - 2.0) ** 2})
    assert tpe.suggest("t40") is None and tpe.is_finished()
    early = suggested[:10]
    late = suggested[-10:]
    err = lambda xs: sum(abs(x - 2.0) for x in xs) / len(xs)  # noqa
    assert err(late) < err(early)   # adaptive phase homes in on x=2


# ---------- end-to-end ----------

def _pbt_trainable(config):
    from ray_tpu.train import Checkpoint, get_context, report
    ctx = get_context()
    step, score = 0, 0.0
    if ctx.restored_checkpoint_dir:
        with open(os.path.join(ctx.restored_checkpoint_dir,
                               "state.json")) as f:
            s = json.load(f)
        step, score = s["step"], s["score"]
    while step < 16:
        step += 1
        score += config["lr"]
        # long enough that four trials overlap when their workers start
        # a second apart (six xdist workers beside the real-size
        # compiles): a trial that ends before a better one has reported
        # finds nobody to exploit, and ``exploit_count`` stays 0
        time.sleep(0.1)
        tmp = tempfile.mkdtemp()
        with open(os.path.join(tmp, "state.json"), "w") as f:
            json.dump({"step": step, "score": score}, f)
        report({"score": score, "training_iteration": step},
               checkpoint=Checkpoint.from_directory(tmp))
        shutil.rmtree(tmp, ignore_errors=True)


def test_pbt_end_to_end(rt):
    storage = tempfile.mkdtemp(prefix="tune_pbt_")
    pbt = PopulationBasedTraining(
        metric="score", mode="max", perturbation_interval=3,
        hyperparam_mutations={"lr": [0.1, 0.5, 1.0]},
        quantile_fraction=0.25, seed=0)
    tuner = Tuner(
        _pbt_trainable,
        # one trial alone at the bottom: of two that tie there, the one
        # that reports is the bottom quantile only if the other is ahead
        param_space={"lr": grid_search([0.1, 0.2, 1.0, 1.0])},
        tune_config=TuneConfig(scheduler=pbt, metric="score",
                               mode="max", max_concurrent_trials=4),
        run_config=RunConfig(storage_path=storage, name="pbt"),
    )
    grid = tuner.fit()
    assert not grid.errors
    assert pbt.exploit_count >= 1
    best = grid.get_best_result("score", mode="max")
    assert best.metrics["score"] >= 16 * 1.0 - 1e-6
    shutil.rmtree(storage, ignore_errors=True)


_FAIL_MARKER = os.path.join(tempfile.gettempdir(),
                            "ray_tpu_tune_resume_marker")


def _flaky_trainable(config):
    from ray_tpu.train import report
    if config["x"] == 1 and not os.path.exists(_FAIL_MARKER):
        with open(_FAIL_MARKER, "w"):
            pass
        raise RuntimeError("injected first-run failure")
    report({"loss": float(config["x"])})


def test_tuner_restore_reruns_failed_trials(rt):
    storage = tempfile.mkdtemp(prefix="tune_resume_")
    if os.path.exists(_FAIL_MARKER):
        os.remove(_FAIL_MARKER)
    tuner = Tuner(
        _flaky_trainable,
        param_space={"x": grid_search([0, 1, 2])},
        run_config=RunConfig(storage_path=storage, name="exp"),
    )
    grid = tuner.fit()
    assert len(grid.errors) == 1
    exp_dir = os.path.join(storage, "exp")
    assert os.path.exists(
        os.path.join(exp_dir, "experiment_state.json"))

    restored = Tuner.restore(exp_dir, _flaky_trainable)
    grid2 = restored.fit()
    assert len(grid2) == 3
    assert not grid2.errors           # failed trial re-ran clean
    assert {r.metrics["loss"] for r in grid2} == {0.0, 1.0, 2.0}
    os.remove(_FAIL_MARKER)
    shutil.rmtree(storage, ignore_errors=True)


def test_hyperband_end_to_end(rt):
    storage = tempfile.mkdtemp(prefix="tune_hb_")

    def trainable(config):
        from ray_tpu.train import report
        for i in range(1, 10):
            time.sleep(0.01)
            report({"loss": config["x"] + 1.0 / i,
                    "training_iteration": i})

    hb = HyperBandScheduler(metric="loss", mode="min", max_t=9,
                            reduction_factor=3)
    tuner = Tuner(
        trainable,
        param_space={"x": grid_search([0.0, 5.0, 10.0, 0.5])},
        tune_config=TuneConfig(scheduler=hb, max_concurrent_trials=4),
        run_config=RunConfig(storage_path=storage, name="hb"),
    )
    grid = tuner.fit()
    assert not grid.errors
    best = grid.get_best_result("loss", mode="min")
    assert best.config["x"] in (0.0, 0.5)
    shutil.rmtree(storage, ignore_errors=True)


def test_bayesopt_concentrates_near_optimum():
    from ray_tpu.tune import (
        BayesOptSearcher, choice, loguniform, randint,
    )

    bo = BayesOptSearcher(
        {"x": uniform(-5, 5)}, metric="loss", mode="min",
        num_samples=36, n_startup=8, seed=7)
    suggested = []
    for i in range(36):
        tid = f"b{i}"
        cfg = bo.suggest(tid)
        assert cfg is not None
        suggested.append(cfg["x"])
        bo.on_trial_complete(tid, {"loss": (cfg["x"] - 2.0) ** 2})
    assert bo.suggest("b36") is None and bo.is_finished()
    err = lambda xs: sum(abs(x - 2.0) for x in xs) / len(xs)  # noqa
    assert err(suggested[-8:]) < err(suggested[:8])

    # Mixed space round-trips through the [0,1]^d encoding.
    bo2 = BayesOptSearcher(
        {"lr": loguniform(1e-5, 1e-1), "layers": randint(1, 9),
         "act": choice(["relu", "gelu"])},
        num_samples=12, n_startup=4, seed=0)
    for i in range(12):
        cfg = bo2.suggest(f"m{i}")
        assert 1e-5 <= cfg["lr"] <= 1e-1
        assert 1 <= cfg["layers"] <= 8
        assert cfg["act"] in ("relu", "gelu")
        bo2.on_trial_complete(
            f"m{i}", {"loss": abs(cfg["lr"] - 1e-3) * cfg["layers"]})


def test_bohb_uses_largest_informative_budget():
    from ray_tpu.tune import BOHBSearcher

    bohb = BOHBSearcher({"x": uniform(-5, 5)}, metric="loss",
                        mode="min", num_samples=40, n_startup=6,
                        seed=5)
    suggested = []
    for i in range(40):
        tid = f"h{i}"
        cfg = bohb.suggest(tid)
        suggested.append(cfg["x"])
        # Two rungs: a noisy budget-1 result and (for half the
        # trials, as successive halving would) a clean budget-3 one.
        noisy = (cfg["x"] - 2.0) ** 2 + (10 if i % 2 else 0)
        bohb.on_trial_result(
            tid, {"loss": noisy, "training_iteration": 1})
        if i % 2 == 0:
            bohb.on_trial_result(
                tid, {"loss": (cfg["x"] - 2.0) ** 2,
                      "training_iteration": 3})
            bohb.on_trial_complete(
                tid, {"loss": (cfg["x"] - 2.0) ** 2,
                      "training_iteration": 3})
        else:
            bohb.on_trial_complete(
                tid, {"loss": noisy, "training_iteration": 1})
    err = lambda xs: sum(abs(x - 2.0) for x in xs) / len(xs)  # noqa
    assert err(suggested[-10:]) < err(suggested[:10])
    # The model must have budget-3 observations and prefer them.
    assert 3 in bohb._budget_obs and len(bohb._budget_obs[3]) >= 6


def test_bohb_with_hyperband_e2e(rt):
    """BOHB pairing: HyperBandScheduler + BOHBSearcher over a real
    Tuner run (reference: TuneBOHB + HyperBandForBOHB)."""
    from ray_tpu.train import report
    from ray_tpu.tune import (
        BOHBSearcher, HyperBandScheduler, TuneConfig, Tuner,
    )

    def trainable(config):
        x = config["x"]
        for step in range(1, 9):
            report({"loss": (x - 2.0) ** 2 + 1.0 / step,
                    "training_iteration": step})

    tuner = Tuner(
        trainable,
        param_space={"x": uniform(-5, 5)},
        tune_config=TuneConfig(
            metric="loss", mode="min", num_samples=10,
            search_alg=BOHBSearcher({"x": uniform(-5, 5)},
                                    metric="loss", mode="min",
                                    num_samples=10, n_startup=4,
                                    seed=1),
            scheduler=HyperBandScheduler(metric="loss", mode="min",
                                         max_t=8)))
    grid = tuner.fit()
    best = grid.get_best_result(metric="loss", mode="min")
    assert best is not None
    assert best.metrics["loss"] < 20
