"""Tuner invariants: the paper's two algorithms (through ``Study.optimize``)
and the TrialScheduler's CMPE duties (memoise, log, contain failures), on
synthetic objectives with known optima (property-based where it pays)."""
import json
import math
from pathlib import Path

import pytest
from _hyp import given, settings, st

from repro.core import SPACES, Study, TrialScheduler, best_from_log, read_log
from repro.core.evaluators import FunctionEvaluator
from repro.core.space import TRAIN_SPACE


def quad_objective(cfg):
    t = 10.0
    t += abs(cfg["mesh_model_parallel"] - 8) * 0.5
    t += abs((cfg["microbatch_size"] or 256) - 32) * 0.02
    t += {"none": 2.0, "dots": 0.0, "full": 1.0}[cfg["remat_policy"]]
    return t


def test_gsft_finds_known_optimum(tmp_path):
    out = Study(log_path=tmp_path / "log.jsonl").optimize(
        "train", "gsft", FunctionEvaluator(quad_objective),
        active_params=["mesh_model_parallel", "microbatch_size", "remat_policy"],
        samples_per_param=4,
    )
    assert out.best_config["mesh_model_parallel"] == 8
    assert out.best_config["remat_policy"] == "dots"
    assert out.best_time <= quad_objective({**TRAIN_SPACE.defaults()})
    assert out.reduction_pct > 0


def test_gsft_finer_pass_improves_or_holds(tmp_path):
    """Phase 2 (finer tuning) may never return something worse than phase 1."""
    out = Study(log_path=tmp_path / "l.jsonl").optimize(
        "train", "gsft", FunctionEvaluator(quad_objective),
        active_params=["mesh_model_parallel", "microbatch_size"],
        samples_per_param=3,
    )
    res = out.detail
    assert res.best_time <= res.phase1_time


def test_crs_bounds_contract_and_improve():
    res = Study().optimize("train", "crs", FunctionEvaluator(quad_objective),
                           m=16, k=4, max_rounds=4, seed=1).detail
    # bounds must contract monotonically per numeric parameter
    for name in ("mesh_model_parallel", "microbatch_size"):
        widths = [hi - lo for (lo, hi) in (b[name] for b in res.bound_history)]
        assert all(w2 <= w1 + 1e-9 for w1, w2 in zip(widths, widths[1:])), widths
    default_t = quad_objective(TRAIN_SPACE.defaults())
    assert res.best_time <= default_t


def test_gsft_beats_or_matches_crs_same_objective():
    """The paper's comparison (§XI): GSFT found better configs than CRS."""
    study = Study()
    g = study.optimize("train", "gsft", FunctionEvaluator(quad_objective),
                       active_params=["mesh_model_parallel", "microbatch_size",
                                      "remat_policy"],
                       samples_per_param=4)
    c = study.optimize("train", "crs", FunctionEvaluator(quad_objective),
                       m=12, k=4, max_rounds=4, seed=0)
    assert g.best_time <= c.best_time + 1e-9


def test_cmpe_logs_and_memoizes(tmp_path):
    calls = []

    def f(cfg):
        calls.append(1)
        return 1.0

    sched = TrialScheduler(FunctionEvaluator(f), log_path=tmp_path / "log.jsonl")
    cfg = TRAIN_SPACE.defaults()
    sched.evaluate(cfg)
    sched.evaluate(cfg)  # memoized — evaluator runs once
    assert len(calls) == 1
    recs = read_log(tmp_path / "log.jsonl")
    assert len(recs) == 2 and recs[1]["cached"]
    assert best_from_log(tmp_path / "log.jsonl")["time_s"] == 1.0


def test_cmpe_failed_trial_is_logged_not_raised(tmp_path):
    def f(cfg):
        raise RuntimeError("injected OOM")

    sched = TrialScheduler(FunctionEvaluator(f), log_path=tmp_path / "log.jsonl")
    t = sched.evaluate(TRAIN_SPACE.defaults())
    assert t == float("inf")
    assert read_log(tmp_path / "log.jsonl")[0]["error"]


def test_tuner_never_returns_worse_than_default():
    """Even a hostile objective (defaults optimal) can't regress the outcome."""

    def hostile(cfg):
        return 1.0 if cfg == TRAIN_SPACE.defaults() else 5.0

    out = Study().optimize("train", "crs", FunctionEvaluator(hostile),
                           m=6, k=2, max_rounds=2)
    assert out.best_time == 1.0
    assert out.best_config == TRAIN_SPACE.defaults()


# --------------------------------------------------------------- properties


@given(st.integers(-10_000, 10_000))
@settings(max_examples=200, deadline=None)
def test_property_snap_idempotent_and_bounded(v):
    for p in TRAIN_SPACE.params:
        if p.numeric:
            s1 = p.snap(v)
            assert p.lo <= s1 <= p.hi
            assert p.snap(s1) == s1  # idempotent
            if getattr(p, "pow2", False) and s1 > 0:
                assert s1 & (s1 - 1) == 0  # power of two


@given(st.data())
@settings(max_examples=50, deadline=None)
def test_property_random_configs_valid(data):
    import random

    rng = random.Random(data.draw(st.integers(0, 2**16)))
    for space in SPACES.values():
        cfg = {p.name: p.sample(rng) for p in space.params}
        snapped = space.snap(cfg)
        assert snapped == space.snap(snapped)
        rc = space.to_run_config(snapped)  # must build a valid RunConfig
        assert rc.mesh_model_parallel >= 1
