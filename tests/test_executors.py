"""Subprocess execution backend (hard per-trial isolation) + the
scheduler-correctness sweep that rode along with it: SIGKILLed hung trials,
crash containment, warm worker reuse, spec serialization, per-future batch
deadlines, over-deadline measurement persistence, robust log readers, and
per-run accounting.

Worker-side functions must be module-level: the spawn start method ships
them to workers by pickle-by-reference.
"""
import json
import os
import time

import pytest

from repro.core import EngineConfig, Study, TrialScheduler, run_session
from repro.core.evaluators import FunctionEvaluator
from repro.core.executors import EvaluatorSpec, SubprocessBackend, make_backend
from repro.core.scheduler import best_from_log, read_log
from repro.core.space import TRAIN_SPACE
from repro.core.strategies import GridFinerStrategy


# ---------------------------------------------------- worker-side functions


def _quad(cfg):
    return 10.0 + abs(cfg.get("x", 0) - 3) * 0.5


def _sleep_forever(cfg):
    time.sleep(60.0)
    return 0.0


def _sleep_3s(cfg):
    time.sleep(3.0)
    return 1.0


def _crash_on_flag(cfg):
    if cfg.get("crash"):
        os._exit(13)  # simulated segfault/OOM-kill: no exception, no cleanup
    return 1.0


def _pid_time(cfg):
    return float(os.getpid())


def _raise_on_flag(cfg):
    if cfg.get("boom"):
        raise RuntimeError("injected evaluator failure")
    return 2.0


def make_pid_evaluator():
    """Factory resolved by dotted path inside workers."""
    return FunctionEvaluator(_pid_time)


def _cfgs(n, **extra):
    return [{"x": i, **extra} for i in range(n)]


# -------------------------------------------------------- subprocess backend


def test_subprocess_matches_inline_on_function_evaluator():
    with TrialScheduler(FunctionEvaluator(_quad)) as inline, TrialScheduler(
        FunctionEvaluator(_quad), isolation="subprocess", max_workers=2
    ) as sub:
        t_inline = inline.evaluate_batch(_cfgs(4))
        t_sub = sub.evaluate_batch(_cfgs(4))
    assert [t.time_s for t in t_sub] == [t.time_s for t in t_inline]
    assert all(t.ok and t.status == "ok" for t in t_sub)
    assert sub.run_stats()["fresh"] == 4


def test_subprocess_kills_hung_trials_within_deadline():
    """Acceptance: sleep-60 trials under timeout 2 are SIGKILLed; the whole
    batch completes in well under N×timeout wall clock."""
    sched = TrialScheduler(
        FunctionEvaluator(_sleep_forever),
        isolation="subprocess", max_workers=2, timeout_s=2.0,
    )
    with sched:
        t0 = time.perf_counter()
        trials = sched.evaluate_batch(_cfgs(2))
        elapsed = time.perf_counter() - t0
    assert elapsed < 10.0, elapsed
    assert all(t.status == "timeout" for t in trials)
    assert all("SIGKILL" in t.error for t in trials)
    assert sched.run_stats()["timeouts"] == 2


def test_subprocess_contains_hard_crash_and_session_continues():
    """os._exit(13) inside a trial becomes a status="error" Trial; the
    scheduler keeps serving later trials and batches."""
    with TrialScheduler(
        FunctionEvaluator(_crash_on_flag), isolation="subprocess", max_workers=2
    ) as sched:
        trials = sched.evaluate_batch(
            [{"x": 0, "crash": True}, {"x": 1}, {"x": 2}]
        )
        assert trials[0].status == "error"
        assert "WorkerCrash" in trials[0].error and "13" in trials[0].error
        assert trials[1].ok and trials[1].time_s == 1.0
        assert trials[2].ok
        # the session survives: a fresh batch still works
        again = sched.evaluate_batch([{"x": 3}])
        assert again[0].ok
    assert sched.run_stats()["errors"] == 1


def test_subprocess_workers_are_reused_warm():
    """With one worker, every trial reports the same pid — the process (and
    whatever device/jit state it built) is paid for once, not per trial."""
    with TrialScheduler(
        FunctionEvaluator(_pid_time), isolation="subprocess", max_workers=1
    ) as sched:
        first = sched.evaluate_batch(_cfgs(3))
        second = sched.evaluate_batch([{"x": 99}])  # across batches too
    pids = {t.time_s for t in first} | {second[0].time_s}
    assert len(pids) == 1
    assert pids != {float(os.getpid())}  # and it is NOT this process


def test_subprocess_retries_evaluator_exception_then_records_error():
    with TrialScheduler(
        FunctionEvaluator(_raise_on_flag), isolation="subprocess",
        max_workers=1, retries=1, infeasible_time=1e6,
    ) as sched:
        trials = sched.evaluate_batch([{"boom": True}, {"x": 1}])
    assert trials[0].status == "error"
    assert "injected evaluator failure" in trials[0].error
    assert trials[0].time_s == 1e6
    assert trials[1].ok and trials[1].time_s == 2.0


def test_evaluator_spec_dotted_path_factory():
    backend = SubprocessBackend(
        spec=EvaluatorSpec.factory("test_executors:make_pid_evaluator")
    )
    with TrialScheduler(
        FunctionEvaluator(_quad),  # parent-side evaluator is NOT used
        backend=backend, max_workers=1,
    ) as sched:
        trial = sched.evaluate_batch([{"x": 0}])[0]
    assert trial.ok
    assert trial.time_s != float(os.getpid())  # ran in the worker


def test_unpicklable_evaluator_raises_helpful_error():
    box = []
    ev = FunctionEvaluator(lambda cfg: box and 1.0 or 2.0)  # closure: unpicklable
    with pytest.raises(TypeError, match="EvaluatorSpec"):
        TrialScheduler(ev, isolation="subprocess")


def test_make_backend_registry():
    assert make_backend("inline").name == "inline"
    assert make_backend("subprocess").name == "subprocess"
    with pytest.raises(ValueError, match="unknown isolation backend"):
        make_backend("threads")


def test_subprocess_tune_end_to_end(tmp_path):
    """A full Study session through the subprocess backend: same optimum
    as inline."""
    out = Study(
        engine=EngineConfig(isolation="subprocess", workers=2),
        log_path=tmp_path / "log.jsonl",
    ).optimize(
        "train", "gsft", FunctionEvaluator(_mesh_objective),
        active_params=["mesh_model_parallel"], samples_per_param=3,
    )
    ref = Study().optimize(
        "train", "gsft", FunctionEvaluator(_mesh_objective),
        active_params=["mesh_model_parallel"], samples_per_param=3,
    )
    assert out.best_config == ref.best_config
    assert out.best_time == ref.best_time


def _mesh_objective(cfg):
    return 10.0 + abs(cfg["mesh_model_parallel"] - 8) * 0.5


# ------------------------------------------- satellite: per-future deadlines


def test_parallel_thread_deadlines_not_cumulative():
    """Four 3s trials under a 0.4s timeout must fail in ~one timeout_s of
    wall clock, not 4 sequential ones (the old cumulative-deadline bug:
    each later future inherited the time earlier result() calls burned)."""
    sched = TrialScheduler(
        FunctionEvaluator(_sleep_3s), max_workers=4, timeout_s=0.4
    )
    t0 = time.perf_counter()
    trials = sched.evaluate_batch(_cfgs(4))
    elapsed = time.perf_counter() - t0
    assert elapsed < 1.2, elapsed  # old behaviour: >= 4 * 0.4 = 1.6s
    assert all(t.status == "timeout" for t in trials)


def _sleep_300ms(cfg):
    time.sleep(0.3)
    return 1.0


def test_queued_trials_are_not_falsely_timed_out():
    """timeout_s is per-trial execution time: trials queued behind a full
    pool must not inherit the batch's age as their own deadline."""
    sched = TrialScheduler(
        FunctionEvaluator(_sleep_300ms), max_workers=2, timeout_s=0.5
    )
    trials = sched.evaluate_batch(_cfgs(4))  # two waves of 0.3s < 0.5s each
    assert all(t.ok for t in trials), [t.error for t in trials]


# ---------------------------- satellite: over-deadline measurement survives


def _slow_but_finishes(cfg):
    time.sleep(0.15)
    return 7.0


def test_over_deadline_measurement_kept_and_persisted(tmp_path):
    cache = tmp_path / "cache.jsonl"
    sched = TrialScheduler(
        FunctionEvaluator(_slow_but_finishes), timeout_s=0.05, cache_path=cache
    )
    score = sched.evaluate({"x": 1})
    assert score == float("inf")  # scalar API still scores it infeasible
    trial = sched.trials[0]
    assert trial.status == "timeout"
    assert trial.time_s == 7.0  # the real measurement is kept...
    assert trial.score == float("inf")  # ...but never ranks

    # ...and persisted: a resume replays it instead of re-paying the trial
    calls = []

    def _counting(cfg):
        calls.append(1)
        return 7.0

    resumed = TrialScheduler(
        FunctionEvaluator(_counting), timeout_s=0.05, cache_path=cache
    )
    replay = resumed.evaluate_batch([{"x": 1}])[0]
    assert calls == []
    assert replay.source == "cache"
    assert replay.status == "timeout" and replay.time_s == 7.0


def test_cached_timeout_rejudged_against_current_deadline(tmp_path):
    """A cache written under a tight deadline must not permanently poison a
    config: replay re-judges the persisted wall against the live timeout."""
    cache = tmp_path / "cache.jsonl"
    sched = TrialScheduler(
        FunctionEvaluator(_slow_but_finishes), timeout_s=0.05, cache_path=cache
    )
    sched.evaluate({"x": 1})
    assert sched.trials[0].status == "timeout"

    relaxed = TrialScheduler(
        FunctionEvaluator(_quad), timeout_s=1.0, cache_path=cache
    )
    replay = relaxed.evaluate_batch([{"x": 1}])[0]
    assert replay.source == "cache"
    assert replay.ok and replay.status == "ok" and replay.time_s == 7.0

    no_deadline = TrialScheduler(FunctionEvaluator(_quad), cache_path=cache)
    assert no_deadline.evaluate({"x": 1}) == 7.0  # scores as a plain result


def test_init_failure_policy_cold_vs_warm():
    """Cold pool: init death raises. Warm pool: transient, up to a streak."""
    backend = SubprocessBackend(spec=EvaluatorSpec(target=_quad, construct=False))
    with pytest.raises(RuntimeError, match="boom"):
        backend._init_failed("boom")  # never been ready -> config error
    backend._ever_ready = True
    backend._init_failures = 0
    backend._init_failed("transient")  # tolerated
    backend._init_failed("transient")
    with pytest.raises(RuntimeError, match="transient"):
        backend._init_failed("transient")  # third consecutive -> raise


def test_legacy_cache_record_without_status_loads_as_ok(tmp_path):
    cache = tmp_path / "cache.jsonl"
    from repro.core.scheduler import config_hash

    cfg = {"x": 5}
    cache.write_text(json.dumps({
        "key": config_hash(cfg), "platform": "train", "tag": "",
        "ts": 0.0, "config": cfg, "time_s": 4.0, "info": {},
    }) + "\n")
    sched = TrialScheduler(FunctionEvaluator(_quad), cache_path=cache)
    trial = sched.evaluate_batch([cfg])[0]
    assert trial.source == "cache" and trial.ok and trial.time_s == 4.0


def test_ok_cache_records_carry_no_status_key(tmp_path):
    """Byte-compat: records for successful trials keep the pre-existing
    schema — status/error keys appear only on timeout records."""
    cache = tmp_path / "cache.jsonl"
    sched = TrialScheduler(FunctionEvaluator(_quad), cache_path=cache)
    sched.evaluate({"x": 1})
    rec = json.loads(cache.read_text().splitlines()[0])
    assert "status" not in rec and "error" not in rec


# ------------------------------------------ satellite: robust log readers


def test_read_log_tolerates_torn_tail_and_filters_platform(tmp_path):
    log = tmp_path / "log.jsonl"
    recs = [
        {"platform": "cell_a", "config": {"x": 1}, "time_s": 1.0, "error": None},
        {"platform": "cell_b", "config": {"x": 2}, "time_s": 2.0, "error": None},
        {"platform": "cell_a", "config": {"x": 3}, "time_s": 3.0, "error": None},
    ]
    with log.open("w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
        f.write('{"platform": "cell_a", "config": {"x": 4}, "time_')  # torn
    assert len(read_log(log)) == 3
    cell_a = read_log(log, platform="cell_a")
    assert [r["time_s"] for r in cell_a] == [1.0, 3.0]
    assert best_from_log(log, platform="cell_a")["time_s"] == 1.0
    assert best_from_log(log, platform="cell_b")["time_s"] == 2.0


def test_best_from_log_raises_clearly_when_nothing_succeeded(tmp_path):
    log = tmp_path / "log.jsonl"
    log.write_text(json.dumps({
        "platform": "train", "config": {}, "time_s": float("inf"),
        "error": "TrialTimeout: ...",
    }) + "\n")
    with pytest.raises(ValueError, match="no successful trials"):
        best_from_log(log)


# ---------------------------------------- satellite: per-run accounting


def test_shared_scheduler_reports_per_run_deltas():
    sched = TrialScheduler(FunctionEvaluator(_mesh_objective))
    r1 = sched.run(GridFinerStrategy(
        TRAIN_SPACE, active_params=["mesh_model_parallel"], samples_per_param=3))
    n1 = sched.num_evaluations
    assert r1.evaluations == n1
    r2 = sched.run(GridFinerStrategy(
        TRAIN_SPACE, active_params=["microbatch_size"], samples_per_param=3))
    assert r2.evaluations == sched.num_evaluations - n1
    assert r2.evaluations < sched.num_evaluations  # NOT the lifetime total


def test_shared_scheduler_tune_outcome_not_inflated():
    sched = TrialScheduler(FunctionEvaluator(_mesh_objective))
    out1 = run_session(sched, "train", "gsft", TRAIN_SPACE,
                       active_params=["mesh_model_parallel"],
                       samples_per_param=3)
    out2 = run_session(sched, "train", "gsft", TRAIN_SPACE,
                       active_params=["microbatch_size"], samples_per_param=3)
    assert out1.evaluations + out2.evaluations == sched.num_evaluations
