"""The paper, end-to-end: auto-tune WordCount's 12 parameters with the
paper's two algorithms plus the model-based TPE strategy, all on measured
wall-clock time, then compare (paper §X/§XI).

    PYTHONPATH=src python examples/tune_wordcount.py
"""
from pathlib import Path

from repro.apps.wordcount import make_evaluator, WORDCOUNT_SPACE
from repro.core import Study


def main():
    evaluator = make_evaluator()
    log = Path("results/examples/wordcount_tune.jsonl")
    # in memory, no shared cache: each algorithm pays for its own trials
    study = Study(log_path=log)

    gsft = study.optimize("train", "gsft", evaluator, space=WORDCOUNT_SPACE,
                          active_params=["replication", "block_tokens",
                                         "num_map_tasks"],
                          samples_per_param=3)
    crs = study.optimize("train", "crs", evaluator, space=WORDCOUNT_SPACE,
                         m=10, k=3, max_rounds=4, seed=0)
    tpe = study.optimize("train", "tpe", evaluator, space=WORDCOUNT_SPACE,
                         max_trials=40, seed=0)

    print(f"default execution time : {gsft.default_time*1e3:8.1f} ms")
    print(f"GSFT  best             : {gsft.best_time*1e3:8.1f} ms "
          f"(-{gsft.reduction_pct:.1f}%, {gsft.evaluations} trials)")
    print(f"CRS   best             : {crs.best_time*1e3:8.1f} ms "
          f"(-{crs.reduction_pct:.1f}%, {crs.evaluations} trials)")
    print(f"TPE   best             : {tpe.best_time*1e3:8.1f} ms "
          f"(-{tpe.reduction_pct:.1f}%, {tpe.evaluations} trials)")
    print("\nGSFT best config (non-defaults):")
    for k, v in gsft.best_config.items():
        if v != WORDCOUNT_SPACE.param(k).default:
            print(f"  {k} = {v}")
    print(f"\ntrial log -> {log}")


if __name__ == "__main__":
    main()
