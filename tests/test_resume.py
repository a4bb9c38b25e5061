"""Crash-resume regression tests for the persistent eval cache, plus the
soft-timeout reporting contract.

A tuning session killed mid-batch (simulated with an evaluator that raises
``KeyboardInterrupt`` after k calls — pytest's Ctrl-C analog, a BaseException
the scheduler deliberately does NOT swallow) must lose nothing: every trial
that completed before the kill was persisted the moment it finished, so the
re-run replays them from the JSONL cache, pays fresh evaluations only for the
remainder, and lands on the incumbent of a never-crashed run. TPE resumes
through its warm-started observation history as well.
"""
import threading
import time

import pytest

from repro.core import TRAIN_SPACE, EngineConfig, Study, TrialScheduler
from repro.core.evaluators import FunctionEvaluator
from repro.core.scheduler import read_log
from repro.core.strategies import CRSStrategy, GridFinerStrategy, TPEStrategy


def quad_objective(cfg):
    t = 10.0
    t += abs(cfg["mesh_model_parallel"] - 8) * 0.5
    t += abs((cfg["microbatch_size"] or 256) - 32) * 0.02
    t += {"none": 2.0, "dots": 0.0, "full": 1.0}[cfg["remat_policy"]]
    return t


class KillAfter:
    """Deterministic objective that simulates the session being killed
    (SIGINT) on the (n+1)-th fresh evaluation."""

    def __init__(self, n, fn=quad_objective):
        self.n = n
        self.fn = fn
        self.calls = 0
        self._lock = threading.Lock()

    def __call__(self, config):
        with self._lock:
            if self.calls >= self.n:
                raise KeyboardInterrupt
            self.calls += 1
        return float(self.fn(config)), {}


class Counting:
    def __init__(self, fn=quad_objective):
        self.fn = fn
        self.calls = 0
        self._lock = threading.Lock()

    def __call__(self, config):
        with self._lock:
            self.calls += 1
        return float(self.fn(config)), {}


def _crs(seed=5):
    return CRSStrategy(TRAIN_SPACE, m=8, k=3, max_rounds=3, seed=seed)


# ------------------------------------------------------------- crash + resume


def test_crash_mid_batch_then_resume_only_pays_remainder(tmp_path):
    cache = tmp_path / "cache.jsonl"

    # reference: the same seeded sweep, never crashed, no cache
    ref_sched = TrialScheduler(Counting())
    ref = ref_sched.run(_crs(), batch_size=4)
    total = ref_sched.fresh_evaluations

    # run 1: killed mid-batch after 7 fresh evaluations
    killed = 7
    sched1 = TrialScheduler(KillAfter(killed), cache_path=cache)
    with pytest.raises(KeyboardInterrupt):
        sched1.run(_crs(), batch_size=4)
    # every completed trial was persisted the moment it finished — the kill
    # landed mid-batch, not at a batch boundary, and still lost nothing
    assert len(cache.read_text().splitlines()) == killed

    # run 2: same command, same cache — replays the 7, pays the remainder
    ev2 = Counting()
    sched2 = TrialScheduler(ev2, cache_path=cache)
    res2 = sched2.run(_crs(), batch_size=4)
    assert ev2.calls == total - killed
    assert sched2.cache_stats()["cache_hits"] == killed
    assert res2.best_config == ref.best_config
    assert res2.best_time == ref.best_time

    # run 3: complete cache — zero fresh evaluations, identical incumbent
    ev3 = Counting()
    sched3 = TrialScheduler(ev3, cache_path=cache)
    res3 = sched3.run(_crs(), batch_size=4)
    assert ev3.calls == 0
    assert sched3.fresh_evaluations == 0
    assert res3.best_config == ref.best_config
    assert res3.best_time == ref.best_time


def test_crash_resume_gsft_full_rerun_zero_fresh(tmp_path):
    cache = tmp_path / "cache.jsonl"
    kw = dict(active_params=["mesh_model_parallel", "remat_policy"],
              samples_per_param=3)

    sched1 = TrialScheduler(KillAfter(5), cache_path=cache)
    with pytest.raises(KeyboardInterrupt):
        sched1.run(GridFinerStrategy(TRAIN_SPACE, **kw), batch_size=3)

    ev2 = Counting()
    sched2 = TrialScheduler(ev2, cache_path=cache)
    res2 = sched2.run(GridFinerStrategy(TRAIN_SPACE, **kw), batch_size=3)
    assert sched2.cache_stats()["cache_hits"] == 5

    ev3 = Counting()
    sched3 = TrialScheduler(ev3, cache_path=cache)
    res3 = sched3.run(GridFinerStrategy(TRAIN_SPACE, **kw), batch_size=3)
    assert ev3.calls == 0
    assert res3.best_config == res2.best_config
    assert res3.best_time == res2.best_time


def test_tpe_crash_resume_warm_history_pays_only_remaining_budget(tmp_path):
    """TPE resumes via warm-started history: cached observations count toward
    max_trials, so the re-run proposes only the unpaid remainder and a
    complete cache proposes nothing at all."""
    cache = tmp_path / "cache.jsonl"
    budget, killed = 20, 9

    sched1 = TrialScheduler(KillAfter(killed), platform="train", cache_path=cache)
    with pytest.raises(KeyboardInterrupt):
        sched1.run(TPEStrategy(TRAIN_SPACE, max_trials=budget, seed=3), batch_size=4)
    assert len(cache.read_text().splitlines()) == killed

    # resume through a Study session: the cache warm-starts the observation
    # history
    ev2 = Counting()
    out2 = Study(cache_path=cache).optimize(
        "train", "tpe", ev2, max_trials=budget, seed=3)
    assert out2.detail.warm_started == killed
    # fresh = remaining budget + the defaults trial a session always measures
    assert ev2.calls <= budget - killed + 1
    assert out2.detail.n_observations >= budget

    # complete cache: nothing fresh, incumbent identical
    ev3 = Counting()
    out3 = Study(cache_path=cache).optimize(
        "train", "tpe", ev3, max_trials=budget, seed=3)
    assert ev3.calls == 0
    assert out3.cache_stats["fresh"] == 0
    assert out3.best_config == out2.best_config
    assert out3.best_time == out2.best_time


def test_tpe_warm_history_at_budget_proposes_nothing():
    history = []
    import random

    rng = random.Random(0)
    for _ in range(12):
        cfg = {p.name: p.sample(rng) for p in TRAIN_SPACE.params}
        history.append((cfg, quad_objective(cfg)))
    strat = TPEStrategy(TRAIN_SPACE, max_trials=12, history=history)
    assert strat.done
    assert strat.ask(8) == []
    best_cfg, best_t = min(history, key=lambda ct: ct[1])
    res = strat.result()
    assert res.warm_started == 12
    assert res.best_time == best_t
    assert res.best_config == TRAIN_SPACE.snap(best_cfg)


# ------------------------------------------------------- timeout reporting


def test_soft_timeout_counted_as_timeout_not_error(tmp_path):
    log = tmp_path / "log.jsonl"

    def slow(cfg):
        time.sleep(0.2)
        return 1.0

    sched = TrialScheduler(FunctionEvaluator(slow), timeout_s=0.05, log_path=log)
    sched.evaluate(TRAIN_SPACE.defaults())
    trial = sched.trials[0]
    assert trial.status == "timeout" and trial.timed_out
    assert sched.timeout_trials == 1
    assert sched.error_trials == 0  # NOT folded into the failure count
    assert sched.run_stats()["timeouts"] == 1
    assert read_log(log)[0]["status"] == "timeout"


def test_abandoned_thread_timeouts_counted_and_logged(tmp_path):
    """Parallel batch: hung workers are abandoned; their trials must be
    reported as timeouts (status + counter), not generic failures."""
    log = tmp_path / "log.jsonl"

    def hang(cfg):
        time.sleep(1.0)
        return 1.0

    sched = TrialScheduler(FunctionEvaluator(hang), max_workers=2,
                           timeout_s=0.1, log_path=log)
    cfgs = [{**TRAIN_SPACE.defaults(), "mesh_model_parallel": mp}
            for mp in (1, 2)]
    trials = sched.evaluate_batch(cfgs)
    assert all(t.status == "timeout" for t in trials)
    assert sched.timeout_trials == 2
    assert sched.error_trials == 0
    assert all(r["status"] == "timeout" for r in read_log(log))


def test_error_trials_not_counted_as_timeouts():
    def boom(cfg):
        raise RuntimeError("injected")

    sched = TrialScheduler(FunctionEvaluator(boom))
    sched.evaluate(TRAIN_SPACE.defaults())
    assert sched.trials[0].status == "error"
    assert sched.error_trials == 1 and sched.timeout_trials == 0


def test_timeouts_surfaced_in_tune_summary():
    def sometimes_slow(cfg):
        if cfg["mesh_model_parallel"] >= 32:
            time.sleep(0.2)
        return float(cfg["mesh_model_parallel"])

    out = Study(engine=EngineConfig(timeout_s=0.1)).optimize(
        "train", "gsft", FunctionEvaluator(sometimes_slow),
        active_params=["mesh_model_parallel"], samples_per_param=7,
    )
    assert out.timeouts > 0
    assert out.summary()["timeouts"] == out.timeouts
