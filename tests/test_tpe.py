"""TPE strategy: registry drop-in, search quality, seeded determinism,
batch-size invariance, constant-liar batch diversity, warm start, and the
>=2x wall-clock speedup from batched acquisition."""
import threading
import time

import pytest

from repro.core import TRAIN_SPACE, Study, TrialScheduler, make_strategy
from repro.core.evaluators import FunctionEvaluator
from repro.core.scheduler import Trial, config_key
from repro.core.strategies import CRSStrategy, TPEStrategy
from repro.core.strategies.tpe import TPEResult


def quad_objective(cfg):
    t = 10.0
    t += abs(cfg["mesh_model_parallel"] - 8) * 0.5
    t += abs((cfg["microbatch_size"] or 256) - 32) * 0.02
    t += {"none": 2.0, "dots": 0.0, "full": 1.0}[cfg["remat_policy"]]
    return t


class CountingEvaluator:
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


def _trial_keys(scheduler):
    return [config_key(t.config) for t in scheduler.trials]


# ----------------------------------------------------------------- registry


def test_tpe_registered_in_strategy_registry():
    for name in ("tpe", "bayes"):
        s = make_strategy(name, TRAIN_SPACE, max_trials=8)
        assert isinstance(s, TPEStrategy)


def test_tune_supports_tpe_algorithm():
    out = Study().optimize("train", "tpe", FunctionEvaluator(quad_objective),
                           max_trials=40, seed=1)
    assert isinstance(out.detail, TPEResult)
    assert out.evaluations >= 1
    assert out.best_time < out.default_time  # beat the all-defaults config
    assert out.detail.rounds >= 1


# ------------------------------------------------------------ search quality


def test_tpe_beats_pure_random_at_equal_budget():
    """Acceptance-shaped check: the model rounds must add value over the
    startup distribution — same budget, same seed family, pure random via a
    single uncontracted CRS round."""
    budget = 48
    study = Study()
    tpe = study.optimize("train", "tpe", FunctionEvaluator(quad_objective),
                         max_trials=budget, seed=1)
    rand = study.optimize("train", "crs", FunctionEvaluator(quad_objective),
                          m=budget, k=4, max_rounds=1, seed=1)
    assert tpe.best_time <= rand.best_time
    assert tpe.best_config["mesh_model_parallel"] == 8  # found the optimum knob


def test_tpe_proposals_respect_space_and_fixed():
    fixed = {"remat_policy": "dots", "scan_layers": True}
    strat = TPEStrategy(TRAIN_SPACE, fixed=fixed, max_trials=24,
                        n_startup=6, seed=2)
    sched = TrialScheduler(FunctionEvaluator(quad_objective))
    sched.run(strat, batch_size=4)
    assert sched.num_evaluations > 0
    for t in sched.trials:
        assert t.config == TRAIN_SPACE.snap(t.config)  # snap-stable values
        for k, v in fixed.items():
            assert t.config[k] == v


# -------------------------------------------------------------- determinism


@pytest.mark.parametrize("strategy_factory", [
    lambda seed: TPEStrategy(TRAIN_SPACE, max_trials=30, n_startup=8, seed=seed),
    lambda seed: CRSStrategy(TRAIN_SPACE, m=10, k=3, max_rounds=3, seed=seed),
], ids=["tpe", "crs"])
def test_fixed_seed_identical_trial_sequences_across_runs(strategy_factory):
    runs = []
    for _ in range(2):
        sched = TrialScheduler(CountingEvaluator())
        sched.run(strategy_factory(seed=11), batch_size=4)
        runs.append(_trial_keys(sched))
    assert runs[0] == runs[1]


@pytest.mark.parametrize("strategy_factory", [
    lambda seed: TPEStrategy(TRAIN_SPACE, max_trials=30, n_startup=8, seed=seed),
    lambda seed: CRSStrategy(TRAIN_SPACE, m=10, k=3, max_rounds=3, seed=seed),
], ids=["tpe", "crs"])
def test_batch_size_1_vs_4_proposes_identical_config_sets(strategy_factory):
    """Acquisition is round-batched: every round is drawn before any of its
    results is consumed, so the proposed-config set cannot depend on how the
    scheduler slices rounds into batches."""
    keys = {}
    for bs in (1, 4):
        sched = TrialScheduler(CountingEvaluator())
        sched.run(strategy_factory(seed=11), batch_size=bs)
        keys[bs] = _trial_keys(sched)
    assert set(keys[1]) == set(keys[4])
    assert len(keys[1]) == len(keys[4])


# ------------------------------------------------- batched acquisition


def test_constant_liar_round_is_diverse():
    """Post-startup, one ask must deliver distinct configs (the lie pushes
    each in-flight proposal into the bad density, repelling repeats)."""
    strat = TPEStrategy(TRAIN_SPACE, max_trials=40, n_startup=8,
                        round_size=8, seed=4)
    startup = strat.ask(None)
    assert len(startup) == 8
    strat.tell([Trial(c, quad_objective(c)) for c in startup])

    model_round = strat.ask(None)
    assert len(model_round) == 8
    assert strat.tag.startswith("tpe/round")
    keys = {config_key(c) for c in model_round}
    assert len(keys) == len(model_round)  # all distinct in-flight
    seen = {config_key(c) for c in startup}
    assert not (keys & seen)  # and none already evaluated


def test_ask_n_batching_speedup_at_least_2x():
    """Acceptance: with round-batched acquisition the scheduler keeps its
    pool full — >=2x wall-clock over batch_size=1 on a slow evaluator."""
    delay = 0.05
    kw = dict(max_trials=24, n_startup=8, round_size=8, seed=0)

    t0 = time.perf_counter()
    serial = TrialScheduler(CountingEvaluator(delay_s=delay))
    serial.run(TPEStrategy(TRAIN_SPACE, **kw), batch_size=1)
    t_serial = time.perf_counter() - t0

    t0 = time.perf_counter()
    parallel = TrialScheduler(CountingEvaluator(delay_s=delay), max_workers=8)
    parallel.run(TPEStrategy(TRAIN_SPACE, **kw), batch_size=8)
    t_parallel = time.perf_counter() - t0

    assert serial.num_evaluations == parallel.num_evaluations
    assert t_serial >= 2.0 * t_parallel, (t_serial, t_parallel)


# ---------------------------------------------------------------- warm start


def test_tpe_warm_start_skips_paid_startup():
    import random

    rng = random.Random(0)
    history = []
    for _ in range(10):
        cfg = {p.name: p.sample(rng) for p in TRAIN_SPACE.params}
        history.append((cfg, quad_objective(cfg)))

    strat = TPEStrategy(TRAIN_SPACE, max_trials=16, n_startup=10,
                        history=history, seed=0)
    assert strat.warm_started == 10
    first = strat.ask(None)
    # history covers the startup budget: the first round is already model-based
    assert strat.tag.startswith("tpe/round")
    assert len(first) <= 6  # only the unpaid remainder


def test_tpe_warm_start_ignores_records_contradicting_fixed():
    base = {p.name: p.default for p in TRAIN_SPACE.params}
    matching = {**base, "remat_policy": "dots"}
    foreign = {**base, "remat_policy": "none"}  # contradicts the pin below
    strat = TPEStrategy(TRAIN_SPACE, fixed={"remat_policy": "dots"},
                        max_trials=8, history=[(matching, 1.0), (foreign, 0.5)])
    assert strat.warm_started == 1  # only the compatible record is history
    assert strat.result().best_time == 1.0


def test_tpe_warm_start_rejects_foreign_space_records():
    """Cache records from another space (e.g. roofline 'train' trials leaking
    into a wordcount session) must not collapse to the defaults config and
    silently eat the trial budget."""
    from repro.apps.wordcount import WORDCOUNT_SPACE

    train_cfg = {p.name: p.default for p in TRAIN_SPACE.params}
    strat = TPEStrategy(WORDCOUNT_SPACE, max_trials=12,
                        history=[(train_cfg, 1.0)] * 20)
    assert strat.warm_started == 0
    assert not strat.done
    assert len(strat.ask(None)) > 0  # full budget still available


def test_tpe_foreign_strategy_history_is_free_evidence_not_budget():
    """gsft/crs records sharing the cache must inform the model (skip random
    startup) but never consume TPE's own trial budget."""
    import random

    rng = random.Random(0)
    history = []
    for _ in range(20):
        cfg = {p.name: p.sample(rng) for p in TRAIN_SPACE.params}
        history.append((cfg, quad_objective(cfg), "gsft/grid"))

    strat = TPEStrategy(TRAIN_SPACE, max_trials=8, n_startup=10,
                        history=history, seed=0)
    assert strat.warm_started == 20
    assert not strat.done  # budget untouched by foreign records
    first = strat.ask(None)
    assert strat.tag.startswith("tpe/round")  # evidence defused the startup
    assert len(first) == 8  # full own budget still available


def test_tpe_budget_survives_shared_cache_with_other_strategy(tmp_path):
    """The documented shared-cache workflow: gsft first, then tpe in the
    same study. TPE must still run its own fresh trials."""
    study = Study.create(tmp_path / "study")
    study.optimize("train", "gsft", FunctionEvaluator(quad_objective),
                   active_params=["mesh_model_parallel", "remat_policy"],
                   samples_per_param=3)

    ev = CountingEvaluator()
    out = study.optimize("train", "tpe", ev, max_trials=12, seed=0)
    assert ev.calls > 0  # budget was NOT pre-consumed by gsft's records
    assert out.detail.warm_started > 0  # but their evidence was used
    assert out.detail.n_observations >= out.detail.warm_started + 12


def test_tpe_infeasible_observations_land_in_bad_group():
    """inf objective values must not break the split or the densities."""
    strat = TPEStrategy(TRAIN_SPACE, max_trials=20, n_startup=6, seed=5)
    startup = strat.ask(None)
    trials = []
    for i, c in enumerate(startup):
        t = float("inf") if i % 2 else quad_objective(c)
        trials.append(Trial(c, t, error="boom" if i % 2 else None,
                            status="error" if i % 2 else "ok"))
    strat.tell(trials)
    nxt = strat.ask(None)  # model round fits on mixed finite/inf history
    assert nxt
    res = strat.result()
    assert res.best_time < float("inf")
