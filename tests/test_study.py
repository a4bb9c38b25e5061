"""The Study API: create/load/open lifecycle, EngineConfig validation,
optimize sessions, multi-session warm start through on_study_attach,
interrupted-session resume (pays only the unpaid remainder), per-session
delta accounting, cells, and the report/reduction table."""
import json
import threading
from pathlib import Path

import pytest

from repro.core import (
    TRAIN_SPACE,
    EngineConfig,
    Study,
)
from repro.core.evaluators import FunctionEvaluator
from repro.core.executors import EvaluatorSpec


def quad_objective(cfg):
    t = 10.0
    t += abs(cfg["mesh_model_parallel"] - 8) * 0.5
    t += abs((cfg["microbatch_size"] or 256) - 32) * 0.02
    t += {"none": 2.0, "dots": 0.0, "full": 1.0}[cfg["remat_policy"]]
    return t


def make_quad_evaluator():
    """Module-level factory — resume() rebuilds evaluators from specs that
    point here by dotted path."""
    return FunctionEvaluator(quad_objective)


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


CRS_KW = dict(m=8, k=3, max_rounds=3, seed=5)
GSFT_KW = dict(active_params=["mesh_model_parallel", "remat_policy"],
               samples_per_param=4)


# ------------------------------------------------------------ lifecycle


def test_create_writes_manifest_and_load_roundtrips(tmp_path):
    eng = EngineConfig(workers=4, timeout_s=30.0, patience=2)
    study = Study.create(tmp_path / "s", engine=eng, seed=7)
    assert (tmp_path / "s" / "study.json").exists()

    loaded = Study.load(tmp_path / "s")
    assert loaded.engine == eng
    assert loaded.seed == 7
    assert loaded.cache_path == tmp_path / "s" / "cache.jsonl"
    assert loaded.log_path == tmp_path / "s" / "trials.jsonl"


def test_create_refuses_to_clobber_existing_study(tmp_path):
    Study.create(tmp_path / "s")
    with pytest.raises(FileExistsError, match="already exists"):
        Study.create(tmp_path / "s")


def test_load_missing_study_raises(tmp_path):
    with pytest.raises(FileNotFoundError, match="no study"):
        Study.load(tmp_path / "nope")


def test_open_creates_then_loads(tmp_path):
    a = Study.open(tmp_path / "s", seed=3)
    assert a.seed == 3
    b = Study.open(tmp_path / "s")  # second open loads, not clobbers
    assert b.seed == 3


def test_engine_config_validated_in_one_place():
    with pytest.raises(ValueError, match="workers"):
        EngineConfig(workers=0)
    with pytest.raises(ValueError, match="isolation"):
        EngineConfig(isolation="threads")
    with pytest.raises(ValueError, match="timeout_s"):
        EngineConfig(timeout_s=-1.0)
    with pytest.raises(ValueError, match="retries"):
        EngineConfig(retries=-1)
    with pytest.raises(ValueError, match="patience"):
        EngineConfig(patience=0)
    with pytest.raises(ValueError, match="batch_size"):
        EngineConfig(batch_size=0)


# ------------------------------------------------------------- optimize


def test_optimize_finds_optimum_and_records_session(tmp_path):
    study = Study.create(tmp_path / "s")
    out = study.optimize("train", "gsft", FunctionEvaluator(quad_objective),
                         **GSFT_KW)
    assert out.best_config["mesh_model_parallel"] == 8
    assert out.best_config["remat_policy"] == "dots"
    assert out.reduction_pct > 0
    # session provenance persisted: one start + one done record
    recs = [json.loads(l) for l in
            (tmp_path / "s" / "sessions.jsonl").read_text().splitlines()]
    assert [r["event"] for r in recs] == ["start", "done"]
    assert recs[0]["platform"] == "train" and recs[0]["algorithm"] == "gsft"
    assert recs[0]["space"] == "train"
    assert recs[1]["summary"]["best_config"] == out.best_config


def test_warm_rerun_of_same_session_is_free(tmp_path):
    s1 = Study.create(tmp_path / "s")
    cold = s1.optimize("train", "gsft", FunctionEvaluator(quad_objective),
                       **GSFT_KW)
    ev = Counting()
    s2 = Study.load(tmp_path / "s")
    warm = s2.optimize("train", "gsft", ev, **GSFT_KW)
    assert ev.calls == 0
    assert warm.cache_stats["fresh"] == 0
    assert warm.cache_stats["cache_hits"] > 0
    assert warm.best_config == cold.best_config
    assert warm.best_time == cold.best_time


def test_budget_maps_onto_strategy_budget_kwarg(tmp_path):
    study = Study.create(tmp_path / "s")
    out = study.optimize("train", "tpe", FunctionEvaluator(quad_objective),
                         budget=10, seed=0)
    # budget = tpe max_trials; +1 for the defaults trial tune always measures
    assert out.evaluations <= 11
    assert out.detail.n_observations >= 10
    with pytest.raises(ValueError, match="budget knob"):
        study.optimize("train", "gsft", FunctionEvaluator(quad_objective),
                       budget=10, **GSFT_KW)


def test_multi_session_history_warm_starts_tpe_for_free(tmp_path):
    """Session 2 (TPE) must seed its model from session 1's (GSFT) records
    through on_study_attach — free evidence, not budget theft."""
    study = Study.create(tmp_path / "s")
    g = study.optimize("train", "gsft", FunctionEvaluator(quad_objective),
                       **GSFT_KW)
    t = study.optimize("train", "tpe", FunctionEvaluator(quad_objective),
                       budget=8, seed=0)
    assert t.detail.warm_started >= g.evaluations  # gsft records ingested
    assert t.evaluations > 0  # ...but tpe still paid its own budget
    # session 3: repeat of session 2 — its own records now fill the budget
    ev = Counting()
    t2 = study.optimize("train", "tpe", ev, budget=8, seed=0)
    assert ev.calls == 0
    assert t2.cache_stats["fresh"] == 0
    assert t2.best_time == t.best_time


def test_on_study_attach_hook_receives_cached_history(tmp_path):
    """The sanctioned seam: a strategy that overrides on_study_attach gets
    the study's cached observations instead of a constructor kwarg."""
    from repro.core.strategies.base import QueueStrategy, register_strategy

    seen = {}

    @register_strategy("_attach_probe")
    class AttachProbe(QueueStrategy):
        tag = "probe"
        supports_history = True

        def __init__(self, space, *, fixed=None):
            super().__init__()

        def on_study_attach(self, history):
            seen["history"] = list(history)

        def _observe(self, trial):
            pass

        def result(self):
            from repro.core.strategies.tpe import TPEResult

            return TPEResult(best_config={}, best_time=float("inf"),
                             rounds=0, evaluations=0)

    try:
        study = Study.create(tmp_path / "s")
        study.optimize("train", "gsft", FunctionEvaluator(quad_objective),
                       **GSFT_KW)
        study.optimize("train", "_attach_probe",
                       FunctionEvaluator(quad_objective))
        assert seen["history"], "hook never received the cached history"
        cfg, time_s, tag = seen["history"][0]
        assert "mesh_model_parallel" in cfg and time_s > 0
    finally:
        from repro.core.strategies.base import STRATEGIES

        STRATEGIES.pop("_attach_probe", None)


# --------------------------------------------------------------- resume


def test_resume_pays_only_the_unpaid_remainder(tmp_path):
    # reference: the same session, never interrupted
    ref = Study.create(tmp_path / "ref").optimize(
        "train", "crs", FunctionEvaluator(quad_objective), **CRS_KW)
    total = ref.cache_stats["fresh"]

    study = Study.create(tmp_path / "s")
    killed = 6
    with pytest.raises(KeyboardInterrupt):
        study.optimize("train", "crs", KillAfter(killed), **CRS_KW)

    resumed = Study.load(tmp_path / "s")
    ev = Counting()
    out = resumed.resume(evaluator=ev)
    assert ev.calls == total - killed  # only the remainder is paid
    assert out.cache_stats["cache_hits"] == killed
    assert out.best_config == ref.best_config
    assert out.best_time == ref.best_time
    # the interrupted session is now closed: nothing further to resume
    with pytest.raises(ValueError, match="nothing to resume"):
        resumed.resume(evaluator=Counting())


def test_resume_rebuilds_evaluator_from_stored_spec(tmp_path):
    study = Study.create(tmp_path / "s")
    killer = KillAfter(4)
    killer.spec = EvaluatorSpec.factory("test_study:make_quad_evaluator")
    with pytest.raises(KeyboardInterrupt):
        study.optimize("train", "crs", killer, **CRS_KW)

    out = Study.load(tmp_path / "s").resume()  # no evaluator passed
    ref = Study.create(tmp_path / "ref").optimize(
        "train", "crs", FunctionEvaluator(quad_objective), **CRS_KW)
    assert out.best_config == ref.best_config
    assert out.best_time == ref.best_time


def test_resume_without_spec_or_evaluator_raises(tmp_path):
    study = Study.create(tmp_path / "s")
    with pytest.raises(KeyboardInterrupt):
        study.optimize("train", "crs", KillAfter(3), **CRS_KW)
    with pytest.raises(ValueError, match="no evaluator recipe"):
        Study.load(tmp_path / "s").resume()


def test_failed_resume_reopens_the_interrupted_session(tmp_path):
    """A resume attempt that itself FAILS (event=failed — e.g. version skew
    broke the recorded strategy args) must not close the original session:
    its unpaid remainder is still owed to a later, fixed resume."""
    study = Study.create(tmp_path / "s")
    with pytest.raises(KeyboardInterrupt):
        study.optimize("train", "crs", KillAfter(4), **CRS_KW)  # session 1

    # a resume attempt that died deterministically: start(resumes=1) + failed
    study2 = Study.load(tmp_path / "s")
    study2._record({"event": "start", "session": 2, "ts": 0.0,
                    "platform": "train", "algorithm": "crs", "space": "train",
                    "args": dict(CRS_KW), "engine": {}, "resumes": 1})
    study2._record({"event": "failed", "session": 2, "ts": 0.0,
                    "error": "RuntimeError: wrong environment"})

    # session 1 is open again: resume targets it, not "nothing to resume"
    out = Study.load(tmp_path / "s").resume(evaluator=Counting())
    ref = Study.create(tmp_path / "ref").optimize(
        "train", "crs", FunctionEvaluator(quad_objective), **CRS_KW)
    assert out.best_config == ref.best_config


def test_cli_open_study_honors_stored_engine(tmp_path):
    """Opening an existing study from a CLI with engine flags at their
    defaults must keep the study's stored EngineConfig; an explicit flag
    overlays ONLY its own field, never resetting the other stored knobs."""
    from argparse import Namespace

    from repro.launch.tune import engine_overrides, open_persistent_study

    def open_study(args):
        return open_persistent_study(args.study, engine_overrides(args))

    stored = EngineConfig(workers=4, timeout_s=120.0)
    Study.create(tmp_path / "s", engine=stored)
    untyped = Namespace(study=tmp_path / "s", jobs=None, isolation=None,
                        trial_timeout=None, retries=None, patience=None,
                        batch=None)
    assert open_study(untyped).engine == stored
    # ...an explicit flag wins for its field but doesn't clobber the rest
    explicit = Namespace(**{**vars(untyped), "jobs": 8})
    merged = open_study(explicit).engine
    assert merged.workers == 8
    assert merged.timeout_s == 120.0  # stored knob survives the override
    # an explicitly-typed default value is a real override too (--jobs 1)
    reset = Namespace(**{**vars(untyped), "jobs": 1})
    assert open_study(reset).engine.workers == 1


@pytest.mark.parametrize("algorithm_args", [
    ["--algorithm", "gsft", "--samples", "2", "--active", "replication"],
    ["--algorithm", "crs", "--m", "4", "--k", "2", "--rounds", "1"],
    ["--algorithm", "tpe", "--budget", "4", "--startup", "2",
     "--round-size", "2"],
], ids=["gsft", "crs", "tpe"])
def test_cli_tune_study_round_trip(tmp_path, capsys, algorithm_args):
    """The tuning CLI stores its session in the --study directory, and a
    warm re-run into that directory replays it: nothing fresh, the same
    incumbent."""
    from repro.launch.tune import main

    study_dir = tmp_path / "study"
    argv = ["--platform", "wordcount", *algorithm_args, "--jobs", "4",
            "--isolation", "inline", "--study", str(study_dir)]
    assert main(argv) == 0
    cold = json.loads(capsys.readouterr().out)
    assert cold["cache_stats"]["fresh"] > 0
    for name in ("study.json", "cache.jsonl", "trials.jsonl", "sessions.jsonl"):
        assert (study_dir / name).exists(), name

    assert main(argv) == 0
    warm = json.loads(capsys.readouterr().out)
    assert warm["cache_stats"]["fresh"] == 0
    assert warm["best_config"] == cold["best_config"]
    assert warm["best_time_s"] == cold["best_time_s"]


def test_resume_replays_an_explicit_history(tmp_path):
    """history= passed to the original session is recorded provenance: the
    resumed session must re-use it, not swap in cache-derived history."""
    import random

    rng = random.Random(0)
    external = [({p.name: p.sample(rng) for p in TRAIN_SPACE.params},
                 50.0 + i) for i in range(3)]
    study = Study.create(tmp_path / "s")
    with pytest.raises(KeyboardInterrupt):
        study.optimize("train", "tpe", KillAfter(4), budget=10, seed=0,
                       history=external)
    start = Study.load(tmp_path / "s").sessions()[0]
    assert len(start["args"]["history"]) == 3  # recorded, not dropped
    out = Study.load(tmp_path / "s").resume(evaluator=Counting())
    # warm start = the 3 external observations + the 4 persisted trials is
    # NOT what the constructor sees — explicit history wins, so the resumed
    # strategy was seeded with exactly the recorded 3
    assert out.detail.warm_started == 3


def test_resume_works_with_none_valued_kwargs(tmp_path):
    """None-valued kwargs (the CLI passes n_startup=None by default) are
    legal JSON and must not be misread as unserializable — the headline
    SIGINT-resume path has to work for a stock CLI TPE session."""
    study = Study.create(tmp_path / "s")
    with pytest.raises(KeyboardInterrupt):
        study.optimize("train", "tpe", KillAfter(5), budget=12,
                       n_startup=None, round_size=8, seed=0)
    start = Study.load(tmp_path / "s").sessions()[0]
    assert "args_dropped" not in start
    assert start["args"]["n_startup"] is None
    out = Study.load(tmp_path / "s").resume(evaluator=Counting())
    assert out.detail.warm_started == 5  # cached trials seeded the model


def test_cell_chips_guard_survives_process_restart(tmp_path):
    """The chip count is persisted with the study: reopening it with a
    conflicting explicit chips must raise, not silently replay the other
    topology's cached measurements; chips=None adopts the stored value."""
    study = Study.create(tmp_path / "s")
    study.cell("llama3.2-1b", "train_4k", chips=512,
               evaluator=FunctionEvaluator(quad_objective))

    reopened = Study.load(tmp_path / "s")  # fresh process: _cells is empty
    with pytest.raises(ValueError, match="chips=512"):
        reopened.cell("llama3.2-1b", "train_4k", chips=256,
                      evaluator=FunctionEvaluator(quad_objective))
    adopted = reopened.cell("llama3.2-1b", "train_4k",
                            evaluator=FunctionEvaluator(quad_objective))
    assert adopted.chips == 512  # no opinion -> stored topology


def test_legacy_history_kwarg_strategy_without_hook_attribute(tmp_path):
    """A protocol-only strategy (no QueueStrategy base, no on_study_attach
    attribute) with supports_history=True must receive history through its
    constructor — the promised legacy seam."""
    from repro.core.strategies.base import STRATEGIES, register_strategy

    seen = {}

    @register_strategy("_legacy_probe")
    class LegacyProbe:  # implements the Strategy protocol directly
        tag = "legacy"
        supports_history = True
        done = True

        def __init__(self, space, *, fixed=None, history=None):
            seen["history"] = list(history or ())

        def ask(self, n=None):
            return []

        def tell(self, trials):
            pass

        def result(self):
            from repro.core.strategies.tpe import TPEResult

            return TPEResult(best_config={}, best_time=float("inf"),
                             rounds=0, evaluations=0)

    try:
        study = Study.create(tmp_path / "s")
        study.optimize("train", "gsft", FunctionEvaluator(quad_objective),
                       **GSFT_KW)
        study.optimize("train", "_legacy_probe",
                       FunctionEvaluator(quad_objective))
        assert seen["history"], "constructor never received the history"
    finally:
        STRATEGIES.pop("_legacy_probe", None)


def test_read_log_missing_path_raises():
    from repro.core.scheduler import read_log

    with pytest.raises(FileNotFoundError):
        read_log(Path("/nonexistent/typo.jsonl"))


def test_optimize_rejects_engine_kwargs_with_clear_error(tmp_path):
    """Engine knobs passed as strategy kwargs get a ValueError pointing at
    EngineConfig, not a confusing TypeError."""
    study = Study.create(tmp_path / "s")
    with pytest.raises(ValueError, match="batch_size.*EngineConfig"):
        study.optimize("train", "gsft", FunctionEvaluator(quad_objective),
                       batch_size=2, **GSFT_KW)
    with pytest.raises(ValueError, match="max_workers.*EngineConfig"):
        study.optimize("train", "gsft", FunctionEvaluator(quad_objective),
                       max_workers=4, **GSFT_KW)


def test_resume_keeps_the_sessions_custom_log_path(tmp_path):
    """A session logging to a custom file (per-cell logs) must keep
    appending there on resume — not silently divert to trials.jsonl."""
    from repro.core.scheduler import read_log

    custom_log = tmp_path / "cell.jsonl"
    study = Study.create(tmp_path / "s")
    cell = study.cell("llama3.2-1b", "train_4k", evaluator=KillAfter(3),
                      log_path=custom_log)
    with pytest.raises(KeyboardInterrupt):
        cell.optimize("crs", **CRS_KW)
    study.close()
    before = len(read_log(custom_log))

    out = Study.load(tmp_path / "s").resume(evaluator=Counting())
    assert out.evaluations > 0
    assert len(read_log(custom_log)) > before  # remainder landed in the file


def test_resume_with_nothing_open_raises(tmp_path):
    study = Study.create(tmp_path / "s")
    study.optimize("train", "gsft", FunctionEvaluator(quad_objective),
                   **GSFT_KW)
    with pytest.raises(ValueError, match="nothing to resume"):
        study.resume(evaluator=Counting())


def test_resume_chain_completion_closes_every_link(tmp_path):
    """Session 3 resumes session 2 which resumed session 1: session 3
    completing pays off the whole chain — nothing is left to resume."""
    study = Study.create(tmp_path / "s")
    with pytest.raises(KeyboardInterrupt):
        study.optimize("train", "crs", KillAfter(3), **CRS_KW)  # session 1
    with pytest.raises(KeyboardInterrupt):
        Study.load(tmp_path / "s").resume(
            evaluator=KillAfter(3))  # session 2, also interrupted
    Study.load(tmp_path / "s").resume(evaluator=Counting())  # session 3: done
    with pytest.raises(ValueError, match="nothing to resume"):
        Study.load(tmp_path / "s").resume(evaluator=Counting())


# ------------------------------------------------------ report / accessors


def test_report_is_the_per_session_reduction_table(tmp_path):
    study = Study.create(tmp_path / "s")
    study.optimize("train", "gsft", FunctionEvaluator(quad_objective),
                   **GSFT_KW)
    study.optimize("train", "crs", FunctionEvaluator(quad_objective), **CRS_KW)
    rep = Study.load(tmp_path / "s").report()  # report survives reload
    assert len(rep["sessions"]) == 2
    assert [r["algorithm"] for r in rep["sessions"]] == ["gsft", "crs"]
    for row in rep["sessions"]:
        assert row["status"] == "done"
        assert row["reduction_pct"] > 0
        assert "cache_stats" in row
    assert rep["best"]["train"]["time_s"] <= min(
        r["best_time_s"] for r in rep["sessions"])


def test_report_marks_interrupted_sessions(tmp_path):
    study = Study.create(tmp_path / "s")
    with pytest.raises(KeyboardInterrupt):
        study.optimize("train", "crs", KillAfter(3), **CRS_KW)
    rep = study.report()
    assert rep["sessions"][0]["status"] == "interrupted"


def test_best_and_trials_filter_by_platform(tmp_path):
    study = Study.create(tmp_path / "s")
    study.optimize("train", "gsft", FunctionEvaluator(quad_objective),
                   **GSFT_KW)
    best = study.best(platform="train")
    assert best["time_s"] == study.best()["time_s"]
    assert best["config"]["mesh_model_parallel"] == 8
    assert study.trials(platform="train")
    assert study.trials(platform="serve") == []
    with pytest.raises(ValueError, match="no successful trials"):
        study.best(platform="serve")


# ----------------------------------------------------------------- cells


def test_cell_sessions_share_scheduler_and_report_deltas(tmp_path):
    """Satellite: a second session on the same (shared) scheduler must report
    ITS OWN cache/evaluation deltas, not scheduler-lifetime totals."""
    study = Study.create(tmp_path / "s")
    cell = study.cell("llama3.2-1b", "train_4k",
                      evaluator=FunctionEvaluator(quad_objective))
    assert study.cell("llama3.2-1b", "train_4k") is cell  # one handle per cell

    a = cell.optimize("gsft", active_params=["mesh_model_parallel"],
                      samples_per_param=3)
    b = cell.optimize("gsft", active_params=["microbatch_size"],
                      samples_per_param=3)
    sched = cell.scheduler()
    # per-session deltas sum to the lifetime totals — no inflation
    assert a.cache_stats["fresh"] + b.cache_stats["fresh"] == sched.fresh_evaluations
    assert b.cache_stats["fresh"] < sched.fresh_evaluations
    # session b re-measured the defaults on the shared scheduler => memo hit
    assert b.cache_stats["memo_hits"] >= 1
    assert a.evaluations + b.evaluations == sched.num_evaluations
    study.close()


def test_cell_repeat_call_with_conflicting_setup_raises(tmp_path):
    """The cached measurements were taken under the first call's setup — a
    repeat cell() may not silently swap chips/evaluator/log_path."""
    study = Study.create(tmp_path / "s")
    study.cell("llama3.2-1b", "train_4k",
               evaluator=FunctionEvaluator(quad_objective))
    with pytest.raises(ValueError, match="different chips"):
        study.cell("llama3.2-1b", "train_4k", chips=512)
    with pytest.raises(ValueError, match="different evaluator"):
        study.cell("llama3.2-1b", "train_4k",
                   evaluator=FunctionEvaluator(lambda c: 1.0))
    # an explicit chips request conflicting with a non-default cell raises
    # too (no "default = no opinion" loophole)
    study.cell("qwen2-72b", "train_4k", chips=512,
               evaluator=FunctionEvaluator(quad_objective))
    with pytest.raises(ValueError, match="different chips"):
        study.cell("qwen2-72b", "train_4k", chips=256)
    assert study.has_cell("qwen2-72b", "train_4k")
    assert not study.has_cell("qwen2-72b", "decode_32k")


def test_failed_session_is_closed_and_does_not_block_resume(tmp_path):
    """A deterministic failure (bad kwarg) must close its session record so
    resume() still reaches the genuinely interrupted session before it."""
    study = Study.create(tmp_path / "s")
    with pytest.raises(KeyboardInterrupt):
        study.optimize("train", "crs", KillAfter(4), **CRS_KW)  # session 1
    with pytest.raises(TypeError):
        study.optimize("train", "crs", Counting(),
                       bogus_kwarg=3, **CRS_KW)  # session 2: fails instantly
    study2 = Study.load(tmp_path / "s")
    assert [r["status"] for r in study2.report()["sessions"]] == [
        "interrupted", "failed"]
    out = study2.resume(evaluator=Counting())  # resumes session 1, not 2
    ref = Study.create(tmp_path / "ref").optimize(
        "train", "crs", FunctionEvaluator(quad_objective), **CRS_KW)
    assert out.best_config == ref.best_config


def test_resume_refuses_lossy_session_record(tmp_path):
    """Constraints that failed to round-trip through the session manifest
    (non-JSON values) must block resume, not be silently dropped."""
    study = Study.create(tmp_path / "s")
    with pytest.raises(KeyboardInterrupt):
        study.optimize("train", "crs", KillAfter(3),
                       fixed={"remat_policy": object()}, **CRS_KW)
    with pytest.raises(ValueError, match="did not round-trip"):
        Study.load(tmp_path / "s").resume(evaluator=Counting())


def test_cells_namespace_the_shared_cache(tmp_path):
    """The same knob dict on two different cells must never collide."""
    study = Study.create(tmp_path / "s")
    slow = study.cell("llama3.2-1b", "train_4k",
                      evaluator=FunctionEvaluator(lambda c: 5.0))
    fast = study.cell("qwen2-72b", "train_4k",
                      evaluator=FunctionEvaluator(lambda c: 1.0))
    a = slow.optimize("gsft", active_params=["mesh_model_parallel"],
                      samples_per_param=2)
    b = fast.optimize("gsft", active_params=["mesh_model_parallel"],
                      samples_per_param=2)
    assert a.best_time == 5.0 and b.best_time == 1.0
    study.close()
