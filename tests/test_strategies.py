"""The ask/tell Strategy + TrialScheduler engine: cache accounting,
parallel-vs-serial equivalence, early stopping, persistent warm-cache
re-runs (zero fresh evaluations), the >=2x parallel wall-clock demo, and
serial-vs-parallel parity of GSFT/CRS."""
import json
import threading
import time

import pytest

from repro.core import (
    TRAIN_SPACE,
    Study,
    TrialScheduler,
    make_strategy,
)
from repro.core.evaluators import FunctionEvaluator
from repro.core.scheduler import read_log
from repro.core.strategies import (
    CRSStrategy,
    CuratedHillclimbStrategy,
    GridFinerStrategy,
    Move,
)


def quad_objective(cfg):
    t = 10.0
    t += abs(cfg["mesh_model_parallel"] - 8) * 0.5
    t += abs((cfg["microbatch_size"] or 256) - 32) * 0.02
    t += {"none": 2.0, "dots": 0.0, "full": 1.0}[cfg["remat_policy"]]
    return t


ACTIVE = ["mesh_model_parallel", "microbatch_size", "remat_policy"]


class CountingEvaluator:
    """Deterministic objective that counts fresh evaluator invocations
    (thread-safely) and can inject per-call latency."""

    def __init__(self, fn=quad_objective, delay_s=0.0):
        self.fn = fn
        self.delay_s = delay_s
        self.calls = 0
        self._lock = threading.Lock()

    def __call__(self, config):
        with self._lock:
            self.calls += 1
        if self.delay_s:
            time.sleep(self.delay_s)
        return float(self.fn(config)), {}


# ------------------------------------------------------------ cache accounting


def test_cache_hit_miss_accounting(tmp_path):
    ev = CountingEvaluator()
    sched = TrialScheduler(ev, cache_path=tmp_path / "cache.jsonl")
    a = TRAIN_SPACE.defaults()
    b = {**a, "mesh_model_parallel": 8}

    sched.evaluate_batch([a, b, a])  # a fresh, b fresh, a = memo hit
    assert ev.calls == 2
    assert sched.cache_stats() == {"fresh": 2, "memo_hits": 1, "cache_hits": 0}

    sched.evaluate(b)  # repeat across batches = memo hit
    assert sched.cache_stats() == {"fresh": 2, "memo_hits": 2, "cache_hits": 0}
    # num_evaluations counts distinct trials, like the legacy CMPE
    assert sched.num_evaluations == 2


def test_warm_cache_rerun_performs_zero_fresh_evaluations(tmp_path):
    """Acceptance: a warm-cache re-run costs nothing fresh."""
    cache = tmp_path / "cache.jsonl"
    cold_ev = CountingEvaluator()
    cold = Study(cache_path=cache).optimize(
        "train", "gsft", cold_ev, active_params=ACTIVE, samples_per_param=3)
    assert cold_ev.calls > 0

    warm_ev = CountingEvaluator()
    warm = Study(cache_path=cache).optimize(
        "train", "gsft", warm_ev, active_params=ACTIVE, samples_per_param=3)
    assert warm_ev.calls == 0  # every trial replayed from the JSONL cache
    assert warm.best_config == cold.best_config
    assert warm.best_time == cold.best_time
    assert warm.cache_stats["fresh"] == 0
    assert warm.cache_stats["cache_hits"] > 0


def test_persistent_cache_is_platform_namespaced(tmp_path):
    cache = tmp_path / "cache.jsonl"
    cfg = TRAIN_SPACE.defaults()
    s1 = TrialScheduler(FunctionEvaluator(lambda c: 1.0),
                        platform="cell_a", cache_path=cache)
    assert s1.evaluate(cfg) == 1.0
    # same knob dict, different cell: must NOT collide
    s2 = TrialScheduler(FunctionEvaluator(lambda c: 2.0),
                        platform="cell_b", cache_path=cache)
    assert s2.evaluate(cfg) == 2.0
    # but the same cell replays from cache
    s3 = TrialScheduler(FunctionEvaluator(lambda c: 99.0),
                        platform="cell_a", cache_path=cache)
    assert s3.evaluate(cfg) == 1.0


def test_cache_survives_torn_tail_write(tmp_path):
    cache = tmp_path / "cache.jsonl"
    s1 = TrialScheduler(CountingEvaluator(), cache_path=cache)
    s1.evaluate(TRAIN_SPACE.defaults())
    with cache.open("a") as f:
        f.write('{"key": "truncated-rec')  # crashed session's torn line
    ev = CountingEvaluator()
    s2 = TrialScheduler(ev, cache_path=cache)
    s2.evaluate(TRAIN_SPACE.defaults())
    assert ev.calls == 0


# ------------------------------------------------- parallel batches + speedup


def test_parallel_matches_serial_results():
    """Deterministic objective: the engine must return identical trials
    regardless of max_workers / batch_size."""
    serial = TrialScheduler(CountingEvaluator())
    parallel = TrialScheduler(CountingEvaluator(), max_workers=8)

    res_s = serial.run(GridFinerStrategy(TRAIN_SPACE, active_params=ACTIVE,
                                         samples_per_param=3))
    res_p = parallel.run(GridFinerStrategy(TRAIN_SPACE, active_params=ACTIVE,
                                           samples_per_param=3), batch_size=8)
    assert res_s.best_config == res_p.best_config
    assert res_s.best_time == res_p.best_time
    assert res_s.phase1_best == res_p.phase1_best
    assert {t.time_s for t in serial.trials} == {t.time_s for t in parallel.trials}


def test_parallel_batches_at_least_2x_faster():
    """Acceptance: >=2x wall-clock reduction on a multi-trial tuning run."""
    delay = 0.05
    strategy_kw = dict(active_params=["mesh_model_parallel"], samples_per_param=6)

    t0 = time.perf_counter()
    serial = TrialScheduler(CountingEvaluator(delay_s=delay))
    serial.run(GridFinerStrategy(TRAIN_SPACE, **strategy_kw))
    t_serial = time.perf_counter() - t0

    t0 = time.perf_counter()
    parallel = TrialScheduler(CountingEvaluator(delay_s=delay), max_workers=8)
    parallel.run(GridFinerStrategy(TRAIN_SPACE, **strategy_kw))
    t_parallel = time.perf_counter() - t0

    assert serial.num_evaluations == parallel.num_evaluations
    assert t_serial >= 2.0 * t_parallel, (t_serial, t_parallel)


# --------------------------------------------------------------- early stop


def test_early_stopping_triggers_on_stale_batches():
    flat = FunctionEvaluator(lambda cfg: 5.0)  # nothing ever improves
    sched = TrialScheduler(flat)
    strategy = GridFinerStrategy(
        TRAIN_SPACE, active_params=["mesh_model_parallel", "attn_block_q"],
        samples_per_param=4,
    )
    res = sched.run(strategy, batch_size=1, patience=3)
    assert res.stopped_early
    # pruned long before the full cartesian grid
    assert sched.num_evaluations <= 5
    assert res.best_time == 5.0


def test_no_early_stop_without_patience():
    sched = TrialScheduler(FunctionEvaluator(lambda cfg: 5.0))
    strategy = GridFinerStrategy(TRAIN_SPACE, active_params=["mesh_model_parallel"],
                                 samples_per_param=3)
    res = sched.run(strategy, batch_size=1)
    assert not res.stopped_early


# -------------------------------------------------- timeout / retry / penalty


def test_retries_then_penalty():
    attempts = []

    def flaky(cfg):
        attempts.append(1)
        raise RuntimeError("injected crash")

    sched = TrialScheduler(FunctionEvaluator(flaky), retries=2,
                           infeasible_time=1e6)
    t = sched.evaluate(TRAIN_SPACE.defaults())
    assert len(attempts) == 3  # 1 try + 2 retries
    assert t == 1e6  # finite infeasible penalty instead of inf
    assert sched.trials[0].error and "injected crash" in sched.trials[0].error


def test_soft_timeout_marks_trial_infeasible():
    def slow(cfg):
        time.sleep(0.2)
        return 1.0

    sched = TrialScheduler(FunctionEvaluator(slow), timeout_s=0.05)
    t = sched.evaluate(TRAIN_SPACE.defaults())
    assert t == float("inf")
    assert "TrialTimeout" in sched.trials[0].error


def test_crs_early_stop_mid_round_keeps_best_so_far():
    """An early stop inside a CRS round must still report the best trial
    seen, not an empty result."""
    sched = TrialScheduler(FunctionEvaluator(quad_objective))
    res = sched.run(CRSStrategy(TRAIN_SPACE, m=12, k=4, max_rounds=4, seed=3),
                    batch_size=3, patience=1)
    assert res.best_config  # non-empty even if stopped before a round boundary
    assert res.best_time == min(t.time_s for t in sched.trials)


def test_clear_caches_clears_before_every_fresh_trial(monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(jax, "clear_caches", lambda: calls.append(1))
    sched = TrialScheduler(CountingEvaluator(), clear_caches_between_trials=True,
                           max_workers=4)
    cfgs = [{**TRAIN_SPACE.defaults(), "mesh_model_parallel": mp}
            for mp in (1, 2, 4)]
    sched.evaluate_batch(cfgs + cfgs[:1])  # 3 fresh + 1 memo hit
    assert len(calls) == 3  # one clear per fresh trial, none for the memo hit


def test_parallel_timeout_returns_promptly_with_hung_worker():
    def hang(cfg):
        time.sleep(1.0)
        return 1.0

    sched = TrialScheduler(FunctionEvaluator(hang), max_workers=2, timeout_s=0.1)
    cfgs = [{**TRAIN_SPACE.defaults(), "mesh_model_parallel": mp}
            for mp in (1, 2)]
    t0 = time.perf_counter()
    trials = sched.evaluate_batch(cfgs)
    elapsed = time.perf_counter() - t0
    assert elapsed < 0.8, elapsed  # did not join the hung workers
    assert all("TrialTimeout" in t.error for t in trials)


# ------------------------------------------ ask/tell parity, serial vs parallel


def test_gsft_askfell_parity_with_legacy_wrapper(tmp_path):
    """The strategy driven in parallel batches must reproduce the serial
    path (one trial at a time in ask order, what the paper's CMPE loop ran)
    exactly on a synthetic objective with a known optimum."""
    serial = TrialScheduler(FunctionEvaluator(quad_objective), max_workers=1,
                            log_path=tmp_path / "l.jsonl")
    res_serial = serial.run(
        GridFinerStrategy(TRAIN_SPACE, active_params=ACTIVE, samples_per_param=4)
    )

    engine = TrialScheduler(FunctionEvaluator(quad_objective), max_workers=4)
    res_engine = engine.run(
        GridFinerStrategy(TRAIN_SPACE, active_params=ACTIVE, samples_per_param=4),
        batch_size=16,
    )
    assert res_serial.best_config == res_engine.best_config
    assert res_serial.best_time == res_engine.best_time
    assert res_serial.grid_sizes == res_engine.grid_sizes
    assert res_engine.best_config["mesh_model_parallel"] == 8  # known optimum
    assert res_engine.best_config["remat_policy"] == "dots"


def test_crs_askfell_parity_with_legacy_wrapper():
    """Serial and parallel-batched CRS draw the same rounds: a round's draws
    are made before any result is consumed."""
    serial = TrialScheduler(FunctionEvaluator(quad_objective), max_workers=1)
    res_serial = serial.run(
        CRSStrategy(TRAIN_SPACE, m=12, k=4, max_rounds=4, seed=7)
    )

    engine = TrialScheduler(FunctionEvaluator(quad_objective), max_workers=4)
    res_engine = engine.run(
        CRSStrategy(TRAIN_SPACE, m=12, k=4, max_rounds=4, seed=7), batch_size=6
    )
    assert res_serial.best_config == res_engine.best_config
    assert res_serial.best_time == res_engine.best_time
    assert res_serial.rounds == res_engine.rounds
    assert res_serial.bound_history == res_engine.bound_history


# ------------------------------------------------------------ hillclimb port


def test_hillclimb_strategy_records_and_best():
    moves = [
        Move("baseline", "defaults", {}),
        Move("mp8", "TP=8 shrinks collectives", {"mesh_model_parallel": 8}),
        Move("bad", "hypothesis that fails", {"mesh_model_parallel": 64}),
    ]
    sched = TrialScheduler(FunctionEvaluator(quad_objective))
    res = sched.run(CuratedHillclimbStrategy(TRAIN_SPACE, moves=moves))
    assert [r["name"] for r in res.records] == ["baseline", "mp8", "bad"]
    assert res.best_name == "mp8"
    assert res.best_config["mesh_model_parallel"] == 8
    assert res.records[1]["hypothesis"] == "TP=8 shrinks collectives"
    assert res.evaluations == 3


def test_hillclimb_records_tolerate_info_echoing_t_step(tmp_path):
    """The roofline evaluator's info dict echoes t_step_s (and report.py
    indexes hbm_penalized/mfu unconditionally) — records must stay sane."""

    def roofy(cfg):
        return 2.0, {"t_step_s": 2.0, "bottleneck": "compute",
                     "roofline_fraction_mfu": 0.4, "hbm_est_gib": 9.0}

    sched = TrialScheduler(roofy)
    res = sched.run(CuratedHillclimbStrategy(
        TRAIN_SPACE, moves=[Move("baseline", "defaults", {})]))
    rec = res.records[0]
    assert rec["t_step_s"] == 2.0
    assert rec["hbm_penalized"] is False
    assert rec["mfu"] == 0.4


def test_hillclimb_failed_move_is_recorded_not_raised():
    def explode(cfg):
        if cfg["mesh_model_parallel"] == 64:
            raise MemoryError("HBM overflow")
        return 1.0

    moves = [Move("ok", "fits", {}), Move("oom", "too big", {"mesh_model_parallel": 64})]
    sched = TrialScheduler(FunctionEvaluator(explode))
    res = sched.run(CuratedHillclimbStrategy(TRAIN_SPACE, moves=moves))
    assert "MemoryError" in res.records[1]["error"]
    assert res.best_name == "ok"


# ------------------------------------------------------------------ registry


def test_make_strategy_registry():
    s = make_strategy("gsft", TRAIN_SPACE, active_params=["mesh_model_parallel"])
    assert isinstance(s, GridFinerStrategy)
    with pytest.raises(ValueError, match="unknown strategy"):
        make_strategy("bayesian", TRAIN_SPACE)


def test_tune_supports_hillclimb_algorithm():
    out = Study().optimize(
        "train", "hillclimb", FunctionEvaluator(quad_objective),
        moves=[("baseline", "defaults", {}),
               ("mp8", "smaller collectives", {"mesh_model_parallel": 8})],
    )
    assert out.best_config["mesh_model_parallel"] == 8
    assert out.reduction_pct > 0


# ------------------------------------------------------------------- logging


def test_batch_log_records_match_legacy_shape(tmp_path):
    log = tmp_path / "log.jsonl"
    sched = TrialScheduler(FunctionEvaluator(quad_objective), log_path=log,
                           max_workers=4)
    cfg = TRAIN_SPACE.defaults()
    sched.evaluate_batch([cfg, cfg], tag="t")
    recs = read_log(log)
    assert len(recs) == 2
    assert recs[0]["cached"] is False and recs[1]["cached"] is True
    assert recs[0]["tag"] == "t"
    assert {"ts", "platform", "config", "time_s", "wall_s", "error"} <= set(recs[0])
