"""Multi-cell tuning driver: tune several (arch × shape) cells in ONE
invocation, all sessions going through one Study (shared persistent
evaluation cache, shared trial log, session provenance per cell).

The paper's Admin tunes one platform at a time; a production fleet has a
matrix of cells (model × context shape) to keep tuned. This driver walks the
matrix through ``Study.cell(arch, shape)`` handles — each cell shares one
scheduler across its sessions and the study-wide cache across cells — so
repeated configurations are free, and a re-run after a crash resumes where
the cache left off.

    PYTHONPATH=src python -m repro.launch.multicell \
        --cells llama3.2-1b:train_4k llama3.2-1b:decode_32k \
        --algorithm gsft --study results/studies/fleet

Emits one summary JSON per cell plus a fleet table on stdout. Without
``--study`` the sessions are stored in ``results/studies/multicell``.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import json
from pathlib import Path

from repro.configs.archs import get_arch
from repro.configs.base import SHAPES
from repro.core import SPACES, Study


def cell_platform(shape_name: str) -> str:
    return "train" if SHAPES[shape_name].kind == "train" else "serve"


def tune_cells(
    cells,
    *,
    study: Study,
    algorithm: str = "gsft",
    chips: int = None,  # None = no opinion (256 on cell creation)
    evaluator_factory=None,
    transfer: str = "off",
    **algo_kwargs,
):
    """Tune each ``arch:shape`` cell through ``study``; returns
    {cell: TuneOutcome}. The study's EngineConfig configures every cell's
    scheduler. ``evaluator_factory(arch, shape, space, platform)`` overrides
    the default RooflineEvaluator per cell (tests use a FunctionEvaluator
    matrix).

    ``transfer`` (off|warm|prior) feeds each cell the earlier cells' sibling
    histories from the shared cache (``Study.histories_for``): the matrix is
    walked in order, so cell N+1 transfers from cells 1..N (and from any cell
    a previous invocation left in the study)."""
    outcomes = {}
    for cell in cells:
        arch_name, sep, shape_name = cell.partition(":")
        if not sep or not shape_name:
            raise SystemExit(
                f"bad cell {cell!r}: expected ARCH:SHAPE, e.g. llama3.2-1b:train_4k"
            )
        if shape_name not in SHAPES:
            raise SystemExit(
                f"bad cell {cell!r}: unknown shape {shape_name!r} "
                f"(known: {sorted(SHAPES)})"
            )
        arch = get_arch(arch_name)
        if SHAPES[shape_name].name in arch.skip_shapes:
            print(f"[{cell}] SKIP (arch skips shape)")
            continue
        platform = cell_platform(shape_name)
        if study.has_cell(arch_name, shape_name):
            # repeat pass over an open study (second algorithm, or a
            # duplicated --cells entry): reuse the handle — and never
            # build a second evaluator for the same cell. An explicit
            # chips request still hits cell()'s conflict guard.
            handle = study.cell(arch_name, shape_name, chips=chips)
        else:
            handle = study.cell(
                arch_name, shape_name, chips=chips,
                evaluator=(
                    evaluator_factory(
                        arch_name, shape_name, SPACES[platform], platform,
                    ) if evaluator_factory else None
                ),
            )
        outcome = handle.optimize(algorithm, transfer=transfer, **algo_kwargs)
        outcomes[cell] = outcome
        s = outcome.summary()
        print(f"[{cell}] best={s['best_time_s']:.4f}s "
              f"default={s['default_time_s']:.4f}s "
              f"reduction={s['reduction_pct']:.1f}% "
              f"evals={s['evaluations']} cache={s.get('cache_stats')}", flush=True)
    return outcomes


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", nargs="+", required=True,
                    metavar="ARCH:SHAPE", help="e.g. llama3.2-1b:train_4k")
    ap.add_argument("--algorithm", "--strategy", dest="algorithm", default="gsft",
                    choices=["gsft", "crs", "tpe", "random", "asha"])
    ap.add_argument("--chips", type=int, default=None,
                    help="chip count for new cells (default 256); an explicit "
                         "value conflicting with a study cell's stored setup "
                         "raises instead of silently reusing it")
    ap.add_argument("--samples", type=int, default=2)
    ap.add_argument("--budget", type=int, default=32,
                    help="tpe per-cell trial budget (shared-cache history counts)")
    ap.add_argument("--seed", type=int, default=0,
                    help="crs/tpe/random/asha rng seed")
    ap.add_argument("--inner", default="random", choices=["random", "tpe"],
                    help="asha inner proposer drawing rung-0 candidates")
    ap.add_argument("--eta", type=float, default=3.0,
                    help="asha promotion factor: rung fidelities r0*eta^k, "
                         "top 1/eta of each rung promoted")
    ap.add_argument("--min-fidelity", type=float, default=1.0 / 9.0,
                    help="asha cheapest rung (fraction of a full trial)")
    ap.add_argument("--max-fidelity", type=float, default=1.0,
                    help="asha top rung (1.0 = the full evaluation)")
    ap.add_argument("--transfer", default="off", choices=["off", "warm", "prior"],
                    help="cross-cell transfer: each cell ingests the earlier "
                         "cells' histories from the shared cache (warm = "
                         "sibling incumbents seed candidates; prior = "
                         "distance-decayed tpe Parzen prior; sibling trials "
                         "never count toward --budget)")
    ap.add_argument("--evaluator-factory", default=None, metavar="PKG.MOD:FN",
                    help="dotted path to an evaluator factory "
                         "fn(arch, shape, space, platform) overriding the "
                         "default RooflineEvaluator per cell (tests/CI use a "
                         "deterministic synthetic matrix)")
    ap.add_argument("--study", type=Path,
                    default=Path("results/studies/multicell"),
                    help="Study directory shared by every cell (cache + log + "
                         "session provenance; created on first use)")
    ap.add_argument("--out", type=Path, default=Path("results/multicell/summary.json"))
    # None defaults = "flag not given" so explicit values can override a
    # persistent study's stored engine without untyped flags clobbering it
    ap.add_argument("--patience", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--jobs", type=int, default=None,
                    help="parallel trials per batch (default 1)")
    ap.add_argument("--trial-timeout", "--timeout", dest="trial_timeout",
                    type=float, default=None,
                    help="per-trial timeout in seconds (hard SIGKILL under "
                         "--isolation subprocess)")
    ap.add_argument("--isolation", default=None,
                    choices=["inline", "subprocess"],
                    help="trial execution backend (see launch/tune.py)")
    ap.add_argument("--surrogate", default=None, choices=["off", "rank"],
                    help="learned cost surrogate: pre-rank TPE acquisition "
                         "at the predicted frontier (see launch/tune.py)")
    args = ap.parse_args(argv)

    if args.algorithm == "gsft":
        algo_kwargs = {"samples_per_param": args.samples}
    elif args.algorithm == "crs":
        algo_kwargs = {"seed": args.seed}
    elif args.algorithm == "random":
        algo_kwargs = {"budget": args.budget, "seed": args.seed}
    elif args.algorithm == "asha":
        # multi-fidelity per cell: --budget caps distinct rung-0 configs
        algo_kwargs = {
            "budget": args.budget, "seed": args.seed, "inner": args.inner,
            "eta": args.eta, "min_fidelity": args.min_fidelity,
            "max_fidelity": args.max_fidelity,
        }
    else:  # tpe — each cell warm-starts from its own slice of the shared cache
        algo_kwargs = {"budget": args.budget, "seed": args.seed}
    from repro.launch.tune import engine_overrides, open_persistent_study

    evaluator_factory = None
    if args.evaluator_factory:
        import importlib

        mod, _, attr = args.evaluator_factory.partition(":")
        if not attr:
            raise SystemExit(
                f"bad --evaluator-factory {args.evaluator_factory!r}: "
                "expected PKG.MOD:FN"
            )
        evaluator_factory = getattr(importlib.import_module(mod), attr)
    # explicitly-typed flags overlay the stored engine per-field; untyped
    # flags don't clobber what the study was configured with
    with open_persistent_study(args.study, engine_overrides(args)) as study:
        outcomes = tune_cells(
            args.cells,
            study=study,
            algorithm=args.algorithm,
            chips=args.chips,
            transfer=args.transfer,
            evaluator_factory=evaluator_factory,
            **algo_kwargs,
        )
    args.out.parent.mkdir(parents=True, exist_ok=True)
    args.out.write_text(json.dumps(
        {cell: o.summary() for cell, o in outcomes.items()}, indent=1, default=str
    ))
    print(f"summary -> {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
