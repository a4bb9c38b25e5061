"""One function per paper table (III–XII + §XI comparison), run on the two
measured platforms. Each returns a list of CSV-able row dicts."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, List

from repro.core import EngineConfig, Study, TrialScheduler
from repro.core.study import TuneOutcome

from benchmarks import platforms

RESULTS = Path("results/benchmarks")

# The engine and persistent evaluation cache every table run uses —
# benchmarks.run sets them from its flags (or --study) so the whole suite
# shares one thread-pool size and one cache.
ENGINE: Dict[str, Any] = {"engine": EngineConfig(), "cache_path": None}


def _scheduler(ev, platform: str) -> TrialScheduler:
    """A scheduler on the suite's engine and cache, for tables that time
    chosen configs one by one."""
    return TrialScheduler(ev, platform=platform,
                          cache_path=ENGINE["cache_path"],
                          **ENGINE["engine"].scheduler_kwargs())


def _study(log_name: str) -> Study:
    """An in-memory Study on the suite's engine and cache whose trial log is
    ``RESULTS / log_name``."""
    return Study(engine=ENGINE["engine"], cache_path=ENGINE["cache_path"],
                 log_path=RESULTS / log_name)


def _eval_for(platform: str):
    if platform == "wordcount":
        return platforms.wordcount_evaluator()
    return platforms.lm_train_evaluator()


def _actives(platform: str):
    return platforms.WC_ACTIVE if platform == "wordcount" else platforms.LM_ACTIVE


# -------------------------------------------------- Tables III / VI: defaults


def table_defaults(platform: str) -> List[Dict[str, Any]]:
    ev, space = _eval_for(platform)
    with _scheduler(ev, platform) as sched:
        t = sched.evaluate(space.defaults(), tag="defaults")
    return [{"table": "III" if platform == "wordcount" else "VI",
             "platform": platform, "config": "all-defaults", "time_s": round(t, 4)}]


# ----------------------------------- Tables IV / VII: one-at-optimal sweeps


def one_opt_candidates(space, name):
    """Candidate 'optimal' values per knob (the paper took these from prior
    manual-tuning work; we sweep each knob's grid and keep the best)."""
    p = space.param(name)
    vals = p.grid(4)
    return [v for v in vals if v != p.default] or [p.default]


def table_one_opt(platform: str) -> List[Dict[str, Any]]:
    ev, space = _eval_for(platform)
    base = space.defaults()
    rows = []
    best_values = {}
    with _scheduler(ev, platform) as sched:
        t_default = sched.evaluate(base, tag="defaults")
        for p in space.params:
            best_t, best_v = t_default, p.default
            for v in one_opt_candidates(space, p.name):
                t = sched.evaluate({**base, p.name: v}, tag=f"one_opt/{p.name}")
                if t < best_t:
                    best_t, best_v = t, v
            impr = 100.0 * (t_default - best_t) / t_default
            best_values[p.name] = best_v
            rows.append({
                "table": "IV" if platform == "wordcount" else "VII",
                "platform": platform, "param": p.name, "tuned_value": best_v,
                "time_s": round(best_t, 4), "improvement_pct": round(impr, 2),
            })
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / f"one_opt_{platform}.json").write_text(
        json.dumps({"default_time": t_default, "best_values": best_values,
                    "rows": rows}, indent=1, default=str))
    return rows


# -------------------------------- Tables V / VIII: all-at-individual-optimal


def table_all_opt(platform: str) -> List[Dict[str, Any]]:
    ev, space = _eval_for(platform)
    path = RESULTS / f"one_opt_{platform}.json"
    if not path.exists():
        table_one_opt(platform)
    prior = json.loads(path.read_text())
    config = space.snap({**space.defaults(), **prior["best_values"]})
    with _scheduler(ev, platform) as sched:
        t_default = sched.evaluate(space.defaults(), tag="defaults")
        t = sched.evaluate(config, tag="all_opt")
    impr = 100.0 * (t_default - t) / t_default
    return [{"table": "V" if platform == "wordcount" else "VIII",
             "platform": platform, "config": "all-at-individual-optimal",
             "time_s": round(t, 4), "improvement_pct": round(impr, 2)}]


# ------------------------------------------------- Tables IX / X: GSFT


def table_gsft(platform: str) -> List[Dict[str, Any]]:
    ev, space = _eval_for(platform)
    out: TuneOutcome = _study(f"gsft_{platform}.jsonl").optimize(
        platform, "gsft", ev,  # real platform name namespaces the cache
        space=space, active_params=_actives(platform), samples_per_param=3,
    )
    (RESULTS / f"gsft_{platform}.json").write_text(json.dumps(out.summary(), indent=1, default=str))
    return [{"table": "IX" if platform == "wordcount" else "X",
             "platform": platform, "algorithm": "gsft",
             "default_time_s": round(out.default_time, 4),
             "tuned_time_s": round(out.best_time, 4),
             "reduction_pct": round(out.reduction_pct, 2),
             "evaluations": out.evaluations}]


# ------------------------------------------------ Tables XI / XII: CRS


def table_crs(platform: str) -> List[Dict[str, Any]]:
    ev, space = _eval_for(platform)
    out = _study(f"crs_{platform}.jsonl").optimize(
        platform, "crs", ev, space=space, m=10, k=3, max_rounds=4, seed=0,
    )
    (RESULTS / f"crs_{platform}.json").write_text(json.dumps(out.summary(), indent=1, default=str))
    return [{"table": "XI" if platform == "wordcount" else "XII",
             "platform": platform, "algorithm": "crs",
             "default_time_s": round(out.default_time, 4),
             "tuned_time_s": round(out.best_time, 4),
             "reduction_pct": round(out.reduction_pct, 2),
             "evaluations": out.evaluations}]


# ---------------------------------------------------------- TPE (model-based)


def table_tpe(platform: str, budget: int = 36) -> List[Dict[str, Any]]:
    """TPE over the full knob set at a GSFT-comparable trial budget.

    ``history=[]`` so that with a shared ``--study`` cache the other
    tables' records can't leak into this table's incumbent — the row must
    report what TPE itself found with its own budget."""
    ev, space = _eval_for(platform)
    out = _study(f"tpe_{platform}.jsonl").optimize(
        platform, "tpe", ev,
        space=space, max_trials=budget, round_size=8, seed=0, history=[],
    )
    (RESULTS / f"tpe_{platform}.json").write_text(json.dumps(out.summary(), indent=1, default=str))
    return [{"table": "tpe",
             "platform": platform, "algorithm": "tpe",
             "default_time_s": round(out.default_time, 4),
             "tuned_time_s": round(out.best_time, 4),
             "reduction_pct": round(out.reduction_pct, 2),
             "evaluations": out.evaluations}]


# --------------------------------- GSFT vs CRS vs TPE shootout (equal budget)


def table_strategy_shootout(platform: str = "wordcount", seed: int = 0) -> List[Dict[str, Any]]:
    """The three strategies head-to-head on one platform. GSFT's grid sets
    the trial budget; CRS and TPE get the same number of trials (CRS may stop
    early on its variation rule — the evaluations column keeps it honest). TPE
    runs with an empty warm-start history so every strategy pays full price.
    Writes ``results/benchmarks/strategy_comparison.json``."""
    ev, space = _eval_for(platform)

    gsft = _study(f"shootout_gsft_{platform}.jsonl").optimize(
        platform, "gsft", ev, space=space, active_params=_actives(platform),
        samples_per_param=3)
    budget = gsft.evaluations
    crs = _study(f"shootout_crs_{platform}.jsonl").optimize(
        platform, "crs", ev, space=space,
        m=max(4, budget // 4), k=3, max_rounds=4, seed=seed)
    # budget - 1 proposals: a session spends one trial on the defaults
    # config, which gsft.evaluations already counts — totals come out equal
    tpe = _study(f"shootout_tpe_{platform}.jsonl").optimize(
        platform, "tpe", ev, space=space, max_trials=budget - 1,
        round_size=8, seed=seed, history=[])

    best_baseline = min(gsft.best_time, crs.best_time)
    rows = []
    for name, out in (("gsft", gsft), ("crs", crs), ("tpe", tpe)):
        rows.append({
            "table": "shootout", "platform": platform, "strategy": name,
            "budget": budget, "evaluations": out.evaluations,
            "default_time_s": round(out.default_time, 4),
            "best_time_s": round(out.best_time, 4),
            "reduction_pct": round(out.reduction_pct, 2),
        })
    rows[-1]["matches_or_beats_baselines"] = tpe.best_time <= best_baseline
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / "strategy_comparison.json").write_text(json.dumps({
        "platform": platform, "budget": budget, "rows": rows,
        "best_configs": {"gsft": gsft.best_config, "crs": crs.best_config,
                         "tpe": tpe.best_config},
    }, indent=1, default=str))
    return rows


# ------------------------- ASHA vs full fidelity (equal config width)


def _log_cost(path: Path) -> Dict[str, float]:
    """Paid evaluation cost of a session from its trial log: fresh ok
    trials only (cache replays cost nothing). ``cost_s`` sums the measured
    per-trial time — fidelity-weighted by construction, since a cheap rung
    runs a corpus prefix — and ``trial_equiv`` sums raw fidelities."""
    from repro.core.scheduler import read_log

    recs = [r for r in read_log(path)
            if not r["cached"] and r.get("status", "ok") == "ok"]
    return {
        "fresh_trials": len(recs),
        "cost_s": sum(float(r["time_s"]) for r in recs),
        "trial_equiv": sum(float(r.get("fidelity", 1.0)) for r in recs),
    }


def table_asha(platform: str = "wordcount", budget: int = 32,
               seed: int = 0) -> List[Dict[str, Any]]:
    """Multi-fidelity ASHA against full-fidelity TPE and CRS at the same
    search width (``budget`` distinct configurations each). The claim under
    test: ASHA lands within 2% of the best full-fidelity incumbent while
    paying no more than half the evaluation cost (sum of fidelity-weighted
    fresh-trial time), because most of its configs die at the 1/9 rung.
    A steep 4-rung ladder (eta=4 from 1/64) is what hits the cost target
    under the eager top-``ceil(n/eta)`` promotion rule: a completion stream
    that improves over time (TPE proposals) keeps entering the top set, so
    shallow ladders over-promote into the expensive full rung. Rows (with
    per-rung trial/promotion counts) are merged into
    ``results/benchmarks/strategy_comparison.json``.

    Every strategy here measures best-of-4 repeats (vs the suite's usual 2):
    the comparison is between incumbents, and ASHA keeps only a handful of
    full-fidelity measurements, so per-trial walltime noise that washes out
    over TPE's 32 full trials would otherwise dominate its reported best."""
    if platform == "wordcount":
        ev, space = platforms.wordcount_evaluator(repeats=4)
    else:
        ev, space = _eval_for(platform)

    crs = _study(f"asha_crs_{platform}.jsonl").optimize(
        platform, "crs", ev, space=space,
        m=max(4, budget // 4), k=3, max_rounds=4, seed=seed)
    tpe = _study(f"asha_tpe_{platform}.jsonl").optimize(
        platform, "tpe", ev, space=space, max_trials=budget,
        round_size=8, seed=seed, history=[])
    asha = _study(f"asha_asha_{platform}.jsonl").optimize(
        platform, "asha", ev, space=space, max_trials=budget,
        inner="tpe", eta=4.0, min_fidelity=1.0 / 64.0, seed=seed)

    # the within-2% verdict compares the *configs* each strategy chose,
    # re-measured back to back under one best-of-8 yardstick — an in-run
    # best is a min over N noisy measurements, which structurally favours
    # the strategy that paid for more full-fidelity trials
    judge, _ = (platforms.wordcount_evaluator(repeats=8)
                if platform == "wordcount" else _eval_for(platform))
    rows = []
    for name, out in (("crs", crs), ("tpe", tpe), ("asha", asha)):
        cost = _log_cost(RESULTS / f"asha_{name}_{platform}.jsonl")
        rows.append({
            "table": "asha", "platform": platform, "strategy": name,
            "fidelity": "multi" if name == "asha" else "full",
            "budget": budget,
            "best_time_s": round(out.best_time, 4),
            "verified_best_s": round(judge(out.best_config)[0], 4),
            "default_time_s": round(out.default_time, 4),
            "reduction_pct": round(out.reduction_pct, 2),
            "fresh_trials": cost["fresh_trials"],
            "cost_s": round(cost["cost_s"], 4),
            "trial_equiv": round(cost["trial_equiv"], 2),
        })
    full_best = min(rows[0]["verified_best_s"], rows[1]["verified_best_s"])
    full_cost = min(r["cost_s"] for r in rows[:2])
    rows[-1]["rungs"] = asha.summary()["rungs"]
    rows[-1]["within_2pct_of_full"] = (
        rows[-1]["verified_best_s"] <= full_best * 1.02)
    rows[-1]["cost_vs_full"] = round(rows[-1]["cost_s"] / full_cost, 3)
    rows[-1]["half_cost_or_less"] = rows[-1]["cost_s"] <= 0.5 * full_cost

    RESULTS.mkdir(parents=True, exist_ok=True)
    comparison = RESULTS / "strategy_comparison.json"
    doc = json.loads(comparison.read_text()) if comparison.exists() else {
        "platform": platform, "rows": []}
    doc["rows"] = [r for r in doc.get("rows", [])
                   if r.get("table") != "asha"] + rows
    comparison.write_text(json.dumps(doc, indent=1, default=str))
    return rows


# ------------------------------------- cross-cell transfer (WordCount matrix)


def table_transfer(budget: int = 24, seed: int = 2) -> List[Dict[str, Any]]:
    """Cross-cell transfer on a WordCount matrix: a half-size-corpus cell
    (``wordcount/wc:1m``) tunes first, then the full-corpus sibling
    (``wordcount/wc:2m``) runs at the same budget with ``transfer`` off vs
    prior. Reports, per mode, the sibling cell's best time and how many fresh
    evaluations it needed to reach the off-run's final incumbent — the
    transfer claim made measurable on the paper's own workload. Rows are
    merged into ``results/benchmarks/strategy_comparison.json``."""
    import shutil
    import tempfile

    from repro.apps.wordcount import make_corpus, make_evaluator

    cell_a, cell_b = "wordcount/wc:1m", "wordcount/wc:2m"
    runs: Dict[str, Dict[str, Any]] = {}
    for mode in ("off", "prior"):
        tmp = Path(tempfile.mkdtemp(prefix=f"wc_transfer_{mode}_"))
        try:
            study = Study.create(tmp / "study")
            # the donor cell gets a deeper sweep — its evidence is the prior
            study.optimize(cell_a, "tpe", make_evaluator(make_corpus(1 << 20)),
                           budget=budget + 12, seed=seed)
            out = study.optimize(cell_b, "tpe",
                                 make_evaluator(make_corpus(1 << 21)),
                                 budget=budget, seed=seed, transfer=mode)
            fresh = [float(r["time_s"]) for r in study.trials(platform=cell_b)
                     if not r["cached"] and r.get("status", "ok") == "ok"]
            runs[mode] = {"outcome": out, "fresh_times": fresh}
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    # walltime measurements carry run-to-run noise; "reached the incumbent"
    # means within 2% of the off-run's final best
    incumbent = runs["off"]["outcome"].best_time * 1.02
    rows = []
    for mode in ("off", "prior"):
        out = runs[mode]["outcome"]
        reached = next((i for i, t in enumerate(runs[mode]["fresh_times"], 1)
                        if t <= incumbent), None)
        rows.append({
            "table": "transfer", "platform": "wordcount-matrix",
            "strategy": "tpe", "transfer": mode, "budget": budget,
            "cell": cell_b.split("/", 1)[1],
            "default_time_s": round(out.default_time, 4),
            "best_time_s": round(out.best_time, 4),
            "reduction_pct": round(out.reduction_pct, 2),
            "evaluations": out.evaluations,
            "evals_to_off_incumbent_2pct": reached,
        })
    off_reached = rows[0]["evals_to_off_incumbent_2pct"] or (budget + 2)
    pri_reached = rows[1]["evals_to_off_incumbent_2pct"] or (budget + 2)
    rows[1]["fewer_evals_than_off"] = pri_reached < off_reached

    RESULTS.mkdir(parents=True, exist_ok=True)
    comparison = RESULTS / "strategy_comparison.json"
    doc = json.loads(comparison.read_text()) if comparison.exists() else {
        "platform": "wordcount", "rows": []}
    doc["rows"] = [r for r in doc.get("rows", [])
                   if r.get("table") != "transfer"] + rows
    comparison.write_text(json.dumps(doc, indent=1, default=str))
    return rows


def table_surrogate(budget: int = 24, seed: int = 3) -> List[Dict[str, Any]]:
    """Learned cost surrogate on the WordCount matrix: the half-size-corpus
    donor cell (``wordcount/wc:1m``) tunes first, then the full-corpus
    sibling (``wordcount/wc:2m``) runs at the same budget with ``surrogate``
    off vs rank (``--transfer`` stays off — the donor's evidence reaches the
    rank run only through the cost model). Reports, per mode, the sibling
    cell's best time and how many fresh evaluations it needed to reach the
    off-run's final incumbent. Rows are merged into
    ``results/benchmarks/strategy_comparison.json``."""
    import shutil
    import tempfile

    from repro.apps.wordcount import make_corpus, make_evaluator

    cell_a, cell_b = "wordcount/wc:1m", "wordcount/wc:2m"
    runs: Dict[str, Dict[str, Any]] = {}
    for mode in ("off", "rank"):
        tmp = Path(tempfile.mkdtemp(prefix=f"wc_surrogate_{mode}_"))
        try:
            study = Study.create(tmp / "study")
            # the donor cell gets a deeper sweep — its trials are the
            # surrogate's training set
            study.optimize(cell_a, "tpe", make_evaluator(make_corpus(1 << 20)),
                           budget=budget + 24, seed=seed)
            # a short random startup (same for both modes — the comparison
            # stays fair) puts most of the budget in model rounds, where the
            # donor-trained surrogate actually gets to steer
            out = study.optimize(cell_b, "tpe",
                                 make_evaluator(make_corpus(1 << 21)),
                                 budget=budget, seed=seed, n_startup=4,
                                 engine=study.engine.replace(surrogate=mode))
            fresh = [float(r["time_s"]) for r in study.trials(platform=cell_b)
                     if not r["cached"] and r.get("status", "ok") == "ok"]
            runs[mode] = {"outcome": out, "fresh_times": fresh}
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    # walltime measurements carry run-to-run noise; "reached the incumbent"
    # means within 2% of the off-run's final best
    incumbent = runs["off"]["outcome"].best_time * 1.02
    rows = []
    for mode in ("off", "rank"):
        out = runs[mode]["outcome"]
        reached = next((i for i, t in enumerate(runs[mode]["fresh_times"], 1)
                        if t <= incumbent), None)
        rows.append({
            "table": "surrogate", "platform": "wordcount-matrix",
            "strategy": "tpe", "surrogate": mode, "budget": budget,
            "cell": cell_b.split("/", 1)[1],
            "default_time_s": round(out.default_time, 4),
            "best_time_s": round(out.best_time, 4),
            "reduction_pct": round(out.reduction_pct, 2),
            "evaluations": out.evaluations,
            "evals_to_off_incumbent_2pct": reached,
        })
    off_reached = rows[0]["evals_to_off_incumbent_2pct"] or (budget + 2)
    rank_reached = rows[1]["evals_to_off_incumbent_2pct"] or (budget + 2)
    rows[1]["fewer_evals_than_off"] = rank_reached < off_reached

    RESULTS.mkdir(parents=True, exist_ok=True)
    comparison = RESULTS / "strategy_comparison.json"
    doc = json.loads(comparison.read_text()) if comparison.exists() else {
        "platform": "wordcount", "rows": []}
    doc["rows"] = [r for r in doc.get("rows", [])
                   if r.get("table") != "surrogate"] + rows
    comparison.write_text(json.dumps(doc, indent=1, default=str))
    return rows


# ------------------------------------- kernel autotuning (default vs tuned)


def table_kernels(budget: int = 10, seed: int = 0) -> List[Dict[str, Any]]:
    """Default vs study-tuned block configs per Pallas kernel, interpret
    mode (kernel bodies execute on CPU: the rows rank block configs for the
    Pallas interpreter and exercise the tuning loop; they are not kernel
    speed on any accelerator). Per kernel at one
    representative shape: a TPE session over the kernel's TunableSpace finds
    an incumbent, then default and tuned configs are re-measured back to
    back on the same evaluator and inputs. Rows are merged into
    ``results/benchmarks/strategy_comparison.json``."""
    from repro.core.kernel_tune import KERNEL_SPACES, make_kernel_evaluator

    shapes = {
        "flash_attention": (2, 256, 4, 2, 64),
        "rwkv6": (2, 160, 3, 32),
        "ssm_scan": (2, 128, 64, 8),
    }
    rows = []
    for kernel, shape in shapes.items():
        ev = make_kernel_evaluator(kernel, shape, repeats=3, seed=seed,
                                   interpret=True)
        space = KERNEL_SPACES[kernel]
        with Study() as study:  # ephemeral: the table re-measures for itself
            out = study.optimize(ev.platform_key(), "tpe", ev, space=space,
                                 budget=budget, seed=seed)
        t_default, _ = ev(space.defaults())
        t_tuned, _ = ev(out.best_config)
        impr = 100.0 * (t_default - t_tuned) / t_default if t_default else 0.0
        rows.append({
            "table": "kernels", "kernel": kernel,
            "shape_class": ev.shape_class(), "mode": "interpret",
            "default_config": space.defaults(),
            "tuned_config": out.best_config,
            "default_time_s": round(t_default, 5),
            "tuned_time_s": round(t_tuned, 5),
            "improvement_pct": round(impr, 2),
            "evaluations": out.evaluations,
        })

    RESULTS.mkdir(parents=True, exist_ok=True)
    comparison = RESULTS / "strategy_comparison.json"
    doc = json.loads(comparison.read_text()) if comparison.exists() else {
        "platform": "wordcount", "rows": []}
    doc["rows"] = [r for r in doc.get("rows", [])
                   if r.get("table") != "kernels"] + rows
    comparison.write_text(json.dumps(doc, indent=1, default=str))
    return rows


# --------------------------------------------------- §XI comparison table


def table_comparison() -> List[Dict[str, Any]]:
    rows = []
    for platform in ("wordcount", "lm_train"):
        g = json.loads((RESULTS / f"gsft_{platform}.json").read_text())
        c = json.loads((RESULTS / f"crs_{platform}.json").read_text())
        rows.append({
            "table": "comparison", "platform": platform,
            "gsft_reduction_pct": g["reduction_pct"],
            "crs_reduction_pct": c["reduction_pct"],
            "gsft_ge_crs": g["reduction_pct"] >= c["reduction_pct"],
        })
    return rows


# ------------------------------------------ §Roofline table (from dry-run)


def table_roofline(dryrun_dir: Path = Path("results/dryrun/single")) -> List[Dict[str, Any]]:
    rows = []
    for f in sorted(dryrun_dir.glob("*.json")):
        c = json.loads(f.read_text())
        if c.get("skipped"):
            rows.append({"table": "roofline", "arch": c["arch"], "shape": c["shape"],
                         "status": "SKIP"})
            continue
        r = c.get("roofline", {})
        rows.append({
            "table": "roofline", "arch": c["arch"], "shape": c["shape"],
            "status": "ok" if c.get("compile_ok") else "FAIL",
            "t_compute_s": round(r.get("t_compute_s", 0), 5),
            "t_memory_s": round(r.get("t_memory_s", 0), 5),
            "t_collective_s": round(r.get("t_collective_s", 0), 5),
            "bottleneck": r.get("bottleneck", ""),
            "mfu_at_step": round(r.get("roofline_fraction_mfu", 0), 4),
            "hbm_est_gib": round(c.get("tpu_hbm_estimate", {}).get("total_gib", 0), 2),
        })
    return rows
