"""Benchmark driver — one section per paper table. Prints CSV rows and writes
JSON artifacts under results/benchmarks/.

    PYTHONPATH=src python -m benchmarks.run            # all tables
    PYTHONPATH=src python -m benchmarks.run --quick    # skip the search tables
"""
from __future__ import annotations

import argparse
import csv
import json
import sys
import time
from pathlib import Path

from benchmarks import tables


def emit(rows):
    if not rows:
        return
    cols = list(rows[0].keys())
    w = csv.DictWriter(sys.stdout, fieldnames=cols)
    w.writeheader()
    for r in rows:
        w.writerow(r)
    sys.stdout.flush()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="defaults + roofline only")
    ap.add_argument("--skip-lm", action="store_true", help="wordcount platform only")
    ap.add_argument("--jobs", type=int, default=None,
                    help="parallel trials per batch (TrialScheduler thread "
                         "pool; default 1)")
    ap.add_argument("--study", type=Path, default=None,
                    help="Study directory — every table run shares its "
                         "persistent evaluation cache (created on first use), "
                         "so a warm re-run of the search tables performs no "
                         "fresh evaluations; without it every table measures "
                         "afresh")
    ap.add_argument("--strategy", default="all",
                    choices=["all", "gsft", "crs", "tpe", "asha"],
                    help="which search strategy's tables to run (default all, "
                         "incl. the GSFT-vs-CRS-vs-TPE shootout)")
    ap.add_argument("--isolation", default=None,
                    choices=["inline", "subprocess"],
                    help="trial execution backend for every table run: "
                         "inline threads (the default) or hard-deadline "
                         "worker processes")
    ap.add_argument("--trial-timeout", "--timeout", dest="trial_timeout",
                    type=float, default=None,
                    help="per-trial timeout in seconds (hard SIGKILL under "
                         "--isolation subprocess)")
    args = ap.parse_args(argv)
    # one validated EngineConfig; --study routes every table's trials into
    # the study's shared cache. Explicitly-typed flags overlay the stored
    # engine per-field; untyped flags don't clobber it.
    from repro.core import EngineConfig
    from repro.launch.tune import engine_overrides, open_persistent_study

    if args.study:
        study = open_persistent_study(args.study, engine_overrides(args))
        tables.ENGINE.update(engine=study.engine, cache_path=study.cache_path)
    else:
        tables.ENGINE.update(engine=EngineConfig(**engine_overrides(args)))

    t0 = time.time()
    all_rows = []
    platforms = ["wordcount"] + ([] if args.skip_lm else ["lm_train"])

    for platform in platforms:
        print(f"\n## Table {'III' if platform == 'wordcount' else 'VI'} — "
              f"{platform}: all-defaults execution time")
        rows = tables.table_defaults(platform)
        emit(rows); all_rows += rows

    want = lambda s: args.strategy in ("all", s)
    if not args.quick:
        for platform in platforms:
            print(f"\n## Table {'IV' if platform == 'wordcount' else 'VII'} — "
                  f"{platform}: one parameter at optimal, rest default")
            rows = tables.table_one_opt(platform)
            emit(rows); all_rows += rows

            print(f"\n## Table {'V' if platform == 'wordcount' else 'VIII'} — "
                  f"{platform}: all parameters at individual optimal")
            rows = tables.table_all_opt(platform)
            emit(rows); all_rows += rows

            if want("gsft"):
                print(f"\n## Table {'IX' if platform == 'wordcount' else 'X'} — "
                      f"{platform}: Grid Search with Finer Tuning")
                rows = tables.table_gsft(platform)
                emit(rows); all_rows += rows

            if want("crs"):
                print(f"\n## Table {'XI' if platform == 'wordcount' else 'XII'} — "
                      f"{platform}: Controlled Random Search")
                rows = tables.table_crs(platform)
                emit(rows); all_rows += rows

            if want("tpe"):
                print(f"\n## §TPE — {platform}: Tree-structured Parzen "
                      f"Estimator (full knob set, GSFT-comparable budget)")
                rows = tables.table_tpe(platform)
                emit(rows); all_rows += rows

        if args.strategy == "all":
            print("\n## §XI comparison — reduction in execution time")
            rows = tables.table_comparison()
            emit(rows); all_rows += rows

            print("\n## §Strategy shootout — GSFT vs CRS vs TPE on WordCount "
                  "(equal trial budgets)")
            rows = tables.table_strategy_shootout("wordcount")
            emit(rows); all_rows += rows

            print("\n## §Cross-cell transfer — WordCount matrix, sibling "
                  "cell with --transfer off vs prior (equal budgets)")
            rows = tables.table_transfer()
            emit(rows); all_rows += rows

            print("\n## §Learned cost surrogate — WordCount matrix, sibling "
                  "cell with --surrogate off vs rank (equal budgets)")
            rows = tables.table_surrogate()
            emit(rows); all_rows += rows

        if args.strategy in ("all", "asha"):
            print("\n## §Multi-fidelity ASHA — vs full-fidelity CRS/TPE on "
                  "WordCount (equal search width, fraction of the cost)")
            rows = tables.table_asha("wordcount")
            emit(rows); all_rows += rows

        if args.strategy == "all":
            print("\n## §Kernel autotuning — default vs study-tuned block "
                  "configs per Pallas kernel (interpret mode)")
            rows = tables.table_kernels()
            emit(rows); all_rows += rows

    print("\n## §Roofline — per (arch × shape) on the 16×16 production mesh "
          "(from the dry-run artifacts)")
    rows = tables.table_roofline()
    emit(rows); all_rows += rows

    out = Path("results/benchmarks/all_tables.json")
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(all_rows, indent=1, default=str))
    print(f"\nDONE in {time.time() - t0:.0f}s -> {out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
