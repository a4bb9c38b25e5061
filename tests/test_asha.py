"""Multi-fidelity ASHA: the fidelity axis (geometric rung ladder, trial
identity, rung-scaled deadlines), the scheduler's async submit/poll seam,
asynchronous promotion (no round barrier), equal-fidelity incumbent rules,
inline-vs-subprocess parity for ASHA sessions, and warm-cache resume.

Worker-side functions must be module-level: the spawn start method ships
them to workers by pickle-by-reference.
"""
import json
import math
import threading
import time
from pathlib import Path

import pytest

from repro.core import EngineConfig, Study, TrialScheduler
from repro.core.evaluators import FunctionEvaluator
from repro.core.fidelity import FidelitySchedule, full_fidelity
from repro.core.scheduler import (
    Trial,
    best_from_log,
    config_key,
    read_log,
    trial_key,
)
from repro.core.space import IntParam, TunableSpace
from repro.core.strategies import AshaStrategy, make_strategy

from _hyp import given, settings, st


def toy_space(hi: int = 40) -> TunableSpace:
    return TunableSpace(
        "toy",
        (IntParam("x", hi // 2, 1, hi), IntParam("y", hi // 2, 1, hi)),
        most_influential=("x",),
    )


# ---------------------------------------------------- worker-side functions


def _quad(cfg, fidelity=1.0):
    x, y = cfg["x"], cfg["y"]
    return (x - 7) ** 2 * 0.001 + (y - 3) ** 2 * 0.0005 + 0.01


def _hang(cfg):
    time.sleep(60.0)
    return 0.0


def make_quad_evaluator():
    return FunctionEvaluator(_quad)


# ------------------------------------------------------------ fidelity axis


def test_rung_ladder_geometric():
    s = FidelitySchedule(1.0 / 9.0, 1.0, 3.0)
    assert s.rungs() == pytest.approx([1.0 / 9.0, 1.0 / 3.0, 1.0])
    # degenerate ladder: min == max collapses to a single full rung
    assert FidelitySchedule(1.0, 1.0, 3.0).rungs() == [1.0]


def test_fidelity_schedule_validates():
    with pytest.raises(ValueError):
        FidelitySchedule(0.0, 1.0, 3.0)
    with pytest.raises(ValueError):
        FidelitySchedule(0.5, 0.25, 3.0)
    with pytest.raises(ValueError):
        FidelitySchedule(0.5, 1.0, 1.0)


def test_trial_key_full_fidelity_is_config_key():
    cfg = {"x": 3, "y": 4}
    assert trial_key(cfg, 1.0) == config_key(cfg)
    low = trial_key(cfg, 1.0 / 3.0)
    assert low != config_key(cfg) and "fidelity=" in low
    assert full_fidelity(1.0) and not full_fidelity(0.999)


def test_low_rung_result_never_replays_as_full(tmp_path):
    """A cached sub-fidelity measurement must miss on a full-fidelity ask."""
    cache = tmp_path / "cache.jsonl"
    calls = []

    def fn(cfg, fidelity=1.0):
        calls.append(fidelity)
        return 1.0 if fidelity >= 1.0 else 0.1

    with TrialScheduler(FunctionEvaluator(fn), cache_path=cache) as s:
        assert s.evaluate({"x": 1}, fidelity=0.25) == pytest.approx(0.1)
    with TrialScheduler(FunctionEvaluator(fn), cache_path=cache) as s:
        # full-fidelity ask pays fresh — the 0.25 record is a different trial
        assert s.evaluate({"x": 1}) == pytest.approx(1.0)
        assert s.cache_stats()["fresh"] == 1
        # while the same sub-fidelity ask replays for free
        assert s.evaluate({"x": 1}, fidelity=0.25) == pytest.approx(0.1)
        assert s.cache_stats()["cache_hits"] == 1
    assert calls == [0.25, 1.0]
    # on disk: sub-fidelity records carry the key, full records stay legacy
    recs = [json.loads(l) for l in cache.read_text().splitlines()]
    fids = sorted(r.get("fidelity", 1.0) for r in recs)
    assert fids == [0.25, 1.0]
    assert "fidelity" not in [r for r in recs if r.get("fidelity", 1.0) == 1.0][0]


# ------------------------------------------------- async submit/poll seam


def test_submit_poll_basic_and_memo():
    with TrialScheduler(FunctionEvaluator(_quad), max_workers=2) as s:
        t1 = s.submit({"x": 7, "y": 3})
        t2 = s.submit({"x": 1, "y": 1})
        t3 = s.submit({"x": 7, "y": 3})  # duplicate of in-flight t1
        got = {}
        while len(got) < 3:
            for ticket, trial in s.poll(timeout=5.0):
                got[ticket] = trial
        assert got[t1].time_s == got[t3].time_s
        assert s.cache_stats()["fresh"] == 2
        assert s.cache_stats()["memo_hits"] == 1
        # a later submit of a finished config resolves instantly via memo
        t4 = s.submit({"x": 1, "y": 1})
        out = s.poll(timeout=0.0)
        assert (t4, got[t2].time_s) in [(k, t.time_s) for k, t in out]


def test_promotion_dispatches_while_same_rung_trial_running():
    """The whole point of ASHA: no round barrier. With one rung-0 trial
    blocked mid-flight, a fast rung-0 completion must promote and its rung-1
    evaluation must *start* while the blocked peer is still running."""
    release = threading.Event()
    blocker_running = threading.Event()
    promoted_while_blocked = threading.Event()
    state = {"first": None}
    lock = threading.Lock()

    def fn(cfg, fidelity=1.0):
        if fidelity < 0.5:  # rung 0
            with lock:
                if state["first"] is None:
                    state["first"] = config_key(cfg)
            if state["first"] == config_key(cfg):
                blocker_running.set()
                release.wait(timeout=30.0)
                return 50.0
            return float(cfg["x"])
        # rung 1 (fidelity 1.0): a promotion reached the evaluator
        if blocker_running.is_set() and not release.is_set():
            promoted_while_blocked.set()
        release.set()  # unblock the straggler so the session drains
        return float(cfg["x"])

    space = toy_space()
    strat = make_strategy(
        "asha", space, seed=5, max_trials=6,
        min_fidelity=1.0 / 3.0, eta=3.0,
    )
    with TrialScheduler(FunctionEvaluator(fn), max_workers=2) as s:
        result = s.run(strat)
    assert promoted_while_blocked.is_set(), (
        "no promotion dispatched while a same-rung trial was still running "
        "— the async path has a round barrier"
    )
    assert result.promotions[0] >= 1
    assert result.rungs == pytest.approx([1.0 / 3.0, 1.0])


def test_asha_inline_subprocess_parity(tmp_path):
    """One worker makes completion order deterministic: the same seed must
    produce identical trial sequences and the same incumbent on both
    backends (async submit/poll runs through each backend's own path)."""
    logs = {}
    for iso in ("inline", "subprocess"):
        log = tmp_path / f"{iso}.jsonl"
        strat = make_strategy(
            "asha", toy_space(), seed=7, max_trials=9,
            min_fidelity=1.0 / 9.0, eta=3.0,
        )
        with TrialScheduler(
            FunctionEvaluator(_quad), max_workers=1, isolation=iso,
            log_path=log,
        ) as s:
            res = s.run(strat)
            logs[iso] = [
                (r["config"]["x"], r["config"]["y"], r.get("fidelity", 1.0))
                for r in read_log(log)
            ]
            if iso == "inline":
                ref = (res.best_config, res.best_time, res.promotions)
            else:
                assert (res.best_config, res.best_time, res.promotions) == ref
    assert logs["inline"] == logs["subprocess"]
    assert any(f < 1.0 for _, _, f in logs["inline"])


def test_hung_rung0_trial_killed_on_scaled_deadline():
    """EngineConfig.timeout_s is the *max-fidelity* deadline; a rung-0 trial
    at fidelity 0.25 gets 0.25x of it and is SIGKILLed on that short
    deadline, not the full one."""
    with TrialScheduler(
        FunctionEvaluator(_hang), isolation="subprocess", max_workers=1,
        timeout_s=8.0,
    ) as s:
        s.submit({"x": 1}, fidelity=0.25)
        done = []
        while not done:
            done = s.poll(timeout=10.0)
        (_, trial), = done
        assert trial.timed_out and not trial.ok
        assert trial.fidelity == 0.25
        # scaled 2s deadline, not the 8s full one. wall_s runs from dispatch
        # to a warm worker, so worker spawn and imports are not in it
        assert "exceeded hard deadline 2.0s" in trial.error, trial.error
        assert 2.0 <= trial.wall_s < 8.0, trial.wall_s


# ------------------------------------------- equal-fidelity incumbent rules


def test_low_rung_score_never_becomes_incumbent(tmp_path):
    log = tmp_path / "log.jsonl"

    def fn(cfg, fidelity=1.0):
        # sub-fidelity scores look (wrongly) amazing
        return 0.001 if fidelity < 1.0 else 1.0 + cfg["x"] * 0.1

    with TrialScheduler(FunctionEvaluator(fn), log_path=log) as s:
        s.evaluate({"x": 1}, fidelity=1.0 / 9.0)
        s.evaluate({"x": 2}, fidelity=1.0 / 9.0)
        s.evaluate({"x": 1})
        best = s.best()
        assert best.fidelity == 1.0 and best.time_s == pytest.approx(1.1)
    rec = best_from_log(log)
    assert rec.get("fidelity", 1.0) == 1.0
    assert rec["time_s"] == pytest.approx(1.1)


def test_patience_ignores_low_rung_improvements():
    """A stream of ever-better low-rung scores must not starve the patience
    counter: staleness is judged at the top fidelity only. If low-rung
    scores set the incumbent, every full-fidelity completion would look
    stale and the run would stop long before the budget."""
    full_calls = []

    def fn(cfg, fidelity=1.0):
        if fidelity < 1.0:
            return 0.0001 * cfg["x"]  # absurdly good, and "improving"
        full_calls.append(cfg["x"])
        return 10.0 - 0.05 * len(full_calls)  # strictly improving

    strat = make_strategy(
        "asha", toy_space(), seed=11, max_trials=9,
        min_fidelity=1.0 / 3.0, eta=3.0,
    )
    with TrialScheduler(FunctionEvaluator(fn), max_workers=1) as s:
        result = s.run_async(strat, patience=2)
    assert not result.stopped_early
    assert result.proposals == 9


def test_infeasible_trial_never_promotes():
    def fn(cfg, fidelity=1.0):
        raise RuntimeError("boom")

    strat = make_strategy(
        "asha", toy_space(), seed=1, max_trials=4,
        min_fidelity=1.0 / 3.0, eta=3.0,
    )
    with TrialScheduler(FunctionEvaluator(fn), max_workers=1) as s:
        result = s.run(strat)
    assert result.promotions == [0, 0]
    assert result.best_config is None


# ------------------------------------------------------- study integration


def test_study_asha_session_and_warm_resume(tmp_path):
    space = toy_space()
    kwargs = dict(
        space=space, budget=9, inner="random", eta=3.0,
        min_fidelity=1.0 / 9.0, seed=3,
    )
    with Study.create(tmp_path / "study", engine=EngineConfig(workers=2)) as st_:
        out = st_.optimize("toy", "asha", FunctionEvaluator(_quad), **kwargs)
        s = out.summary()
        # rung/promotion provenance lands in the summary (and sessions.jsonl)
        assert s["best_fidelity"] == 1.0
        assert [r["rung"] for r in s["rungs"]] == [0, 1, 2]
        assert s["rungs"][0]["launched"] == 9
        assert sum(r["promoted"] for r in s["rungs"]) > 0
        rep = st_.report()
        assert "probe_cache" in rep
        assert any("rungs" in r for r in rep["sessions"])
    # sessions.jsonl carries the rung table for post-hoc tooling
    lines = [json.loads(l)
             for l in (tmp_path / "study" / "sessions.jsonl").read_text().splitlines()]
    done = [l for l in lines if l.get("event") == "done"]
    assert done and "rungs" in done[-1]["summary"]

    # a warm re-run replays every rung from the cache: zero fresh work
    with Study.load(tmp_path / "study") as st2:
        out2 = st2.optimize("toy", "asha", FunctionEvaluator(_quad), **kwargs)
        s2 = out2.summary()
        assert s2["cache_stats"]["fresh"] == 0
        assert s2["best_config"] == s["best_config"]


def test_study_incumbent_requires_top_fidelity(tmp_path):
    """If ASHA's best never reached the top rung (tiny budget), the session
    falls back to the defaults measured at top fidelity rather than
    crowning a cheap-rung score."""

    def fn(cfg, fidelity=1.0):
        return 0.001 if fidelity < 1.0 else 5.0

    with Study(engine=EngineConfig(workers=1)) as st_:
        out = st_.optimize(
            "toy", "asha", FunctionEvaluator(fn), space=toy_space(),
            budget=1, inner="random", eta=3.0, min_fidelity=1.0 / 3.0, seed=0,
        )
        assert out.summary()["best_time_s"] == pytest.approx(5.0)


# ----------------------------------------------------------- property tests


@settings(max_examples=60, deadline=None)
@given(
    st.floats(min_value=0.01, max_value=1.0),
    st.floats(min_value=0.0, max_value=1.0),
    st.floats(min_value=1.1, max_value=6.0),
)
def test_prop_rungs_sorted_and_bounded(min_f, frac, eta):
    max_f = min_f + (1.0 - min_f) * frac
    rungs = FidelitySchedule(min_f, max_f, eta).rungs()
    assert rungs[0] == min_f or len(rungs) == 1
    assert rungs[-1] == max_f
    assert all(a < b for a, b in zip(rungs, rungs[1:]))
    assert all(min_f <= r <= max_f for r in rungs)


@settings(max_examples=40, deadline=None)
@given(st.integers(min_value=1, max_value=40),
       st.floats(min_value=1.5, max_value=5.0),
       st.integers(min_value=0, max_value=10_000))
def test_prop_promotions_are_ceil_n_over_eta(n, eta, seed):
    """Feed all n rung-0 completions before asking for work: exactly
    ceil(n/eta) distinct configs must then hold promotions out of rung 0."""
    strat = AshaStrategy(
        toy_space(200), max_trials=n, min_fidelity=1.0 / 4.0, eta=eta,
        seed=seed,
    )
    jobs = strat.next_jobs(n)
    assert len(jobs) == n and all(j.rung == 0 for j in jobs)
    for i, job in enumerate(jobs):
        strat.on_result(job, Trial(config=job.config, time_s=float((i * 7) % n),
                                   fidelity=job.fidelity))
    promoted = strat.next_jobs(10 * n)
    assert all(j.rung == 1 for j in promoted)
    assert len(promoted) == math.ceil(n / eta)


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=0, max_value=10_000),
       st.integers(min_value=2, max_value=18))
def test_prop_job_stream_is_pure_function_of_seed_and_order(seed, n):
    """Two strategies with the same seed, driven with the same completion
    order and scores, must emit byte-identical job streams."""

    def drive(strat):
        stream, pending = [], []
        while True:
            jobs = strat.next_jobs(2)
            for j in jobs:
                stream.append((config_key(j.config), j.rung, j.fidelity))
                pending.append(j)
            if not pending:
                break
            j = pending.pop(0)  # FIFO completion = deterministic order
            score = float(sum(hash(c) % 97 for c in (config_key(j.config),)))
            strat.on_result(j, Trial(config=j.config, time_s=score,
                                     fidelity=j.fidelity))
        return stream

    mk = lambda: AshaStrategy(toy_space(50), max_trials=n,
                              min_fidelity=1.0 / 9.0, eta=3.0, seed=seed)
    assert drive(mk()) == drive(mk())
