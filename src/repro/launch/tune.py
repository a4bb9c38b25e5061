"""Tuning driver — the paper's Admin box: pick platform × algorithm, run one
Study session through the ask/tell Strategy + TrialScheduler engine.

All state (persistent evaluation cache, trial log, session provenance) lives
in one Study directory. Roofline evaluator (production mesh, AOT — needs the
512 fake devices, so run it the same way as the dry-run):

    PYTHONPATH=src python -m repro.launch.tune --platform train \
        --algorithm gsft --arch qwen2-72b --shape train_4k --evaluator roofline \
        --study results/studies/train

Walltime evaluator on the paper's WordCount job (CPU-measured, the faithful
reproduction), four trials at a time:

    PYTHONPATH=src python -m repro.launch.tune --platform wordcount \
        --algorithm crs --jobs 4 --study results/studies/wc

A warm re-run of the same command performs zero fresh evaluations, and an
interrupted run resumes from everything it already paid. TPE (model-based,
batched acquisition) warm-starts its observation history from the same study:

    PYTHONPATH=src python -m repro.launch.tune --platform wordcount \
        --strategy tpe --budget 48 --jobs 4 --study results/studies/wc

Without ``--study`` the session is stored in ``results/studies/tune``.
"""
import os

if "--evaluator" in __import__("sys").argv and "roofline" in __import__("sys").argv:
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import json
from pathlib import Path

from repro.configs.base import SHAPES
from repro.configs.archs import ARCH_NAMES, get_arch
from repro.core import SPACES, EngineConfig, Study
from repro.core.evaluators import RooflineEvaluator


def add_engine_args(ap: argparse.ArgumentParser, default_study: Path):
    """The study directory and the engine knobs shared by every driver that
    runs the TrialScheduler. The knobs overlay the study's stored
    EngineConfig (see ``open_persistent_study``)."""
    ap.add_argument("--study", type=Path, default=default_study,
                    help="Study directory owning cache + log + session "
                         f"provenance (created on first use; default "
                         f"{default_study})")
    # engine flags default to None (= "not given") so an explicitly-typed
    # value — even one equal to the engine default, like --jobs 1 — is
    # distinguishable and can override a persistent study's stored engine
    ap.add_argument("--jobs", type=int, default=None,
                    help="parallel trials per batch (thread pool; default 1)")
    ap.add_argument("--batch", type=int, default=None,
                    help="max configs per ask() batch (default: whole phase)")
    ap.add_argument("--patience", type=int, default=None,
                    help="stop when best hasn't improved in N batches")
    ap.add_argument("--trial-timeout", "--timeout", dest="trial_timeout",
                    type=float, default=None,
                    help="per-trial timeout in seconds (timeout => infeasible; "
                         "hard SIGKILL under --isolation subprocess)")
    ap.add_argument("--retries", type=int, default=None,
                    help="per-trial retries before recording a failure "
                         "(default 0)")
    ap.add_argument("--isolation", default=None,
                    choices=["inline", "subprocess"],
                    help="trial execution backend: inline threads (soft "
                         "timeouts, the default) or worker processes (hard "
                         "deadlines, crash containment, warm reuse)")
    ap.add_argument("--pin-devices", dest="pin_devices", type=int, default=None,
                    help="restrict each subprocess worker to ONE of N device "
                         "slots (env set before the worker's first jax "
                         "import), so N workers run N truly concurrent "
                         "device trials; requires --isolation subprocess")
    ap.add_argument("--prefilter", default=None, choices=["off", "static"],
                    help="static feasibility gate at propose time: 'static' "
                         "rejects provably-doomed configs (clamp aliases, "
                         "VMEM/HBM overflow) as infeasible_static records "
                         "without spawning a worker (default off)")
    ap.add_argument("--surrogate", default=None, choices=["off", "rank"],
                    help="learned cost surrogate over the study cache: "
                         "'rank' makes TPE over-sample acquisition "
                         "candidates and propose only the model-predicted "
                         "frontier, training on local + sibling-cell "
                         "observations (default off)")


def roofline_platform_key(platform: str, arch: str, shape: str,
                          chips: int) -> str:
    """Per-cell cache namespace (same discipline as Study.cell), with the
    chip count baked in when non-default — runs against different topologies
    must never replay each other's cached measurements."""
    key = f"{platform}/{arch}:{shape}"
    return key if chips == 256 else f"{key}@{chips}c"


def engine_overrides(args) -> dict:
    """EngineConfig fields for exactly the engine flags the user typed."""
    flag_to_field = {
        "jobs": "workers",
        "isolation": "isolation",
        "trial_timeout": "timeout_s",
        "retries": "retries",
        "patience": "patience",
        "batch": "batch_size",
        "pin_devices": "pin_devices",
        "prefilter": "prefilter",
        "surrogate": "surrogate",
    }
    return {
        field: getattr(args, flag)
        for flag, field in flag_to_field.items()
        if getattr(args, flag, None) is not None
    }


def open_persistent_study(path: Path, overrides: dict) -> Study:
    """Open (or create) the study at ``path``, overlaying exactly the engine
    flags the CLI user typed onto the study's stored engine — an untyped
    flag never resets a stored knob (e.g. hard subprocess deadlines the
    study was configured with), while an explicit flag always wins, even at
    its default value. Shared by every ``--study``-taking driver."""
    if (Path(path) / Study.MANIFEST).exists():
        study = Study.load(path)
        if overrides:
            study.engine = study.engine.replace(**overrides)
        return study
    return Study.create(path, engine=EngineConfig(**overrides))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default="train", choices=["train", "serve", "wordcount"])
    ap.add_argument("--algorithm", "--strategy", dest="algorithm", default="gsft",
                    choices=["gsft", "crs", "tpe", "random", "asha"])
    ap.add_argument("--arch", default="llama3.2-1b", choices=ARCH_NAMES)
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--evaluator", default="roofline", choices=["roofline", "walltime"])
    ap.add_argument("--chips", type=int, default=256)
    ap.add_argument("--active", nargs="*", default=None, help="grid knobs (gsft)")
    ap.add_argument("--samples", type=int, default=3)
    ap.add_argument("--m", type=int, default=12, help="crs draws per round")
    ap.add_argument("--k", type=int, default=4, help="crs survivors")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--budget", type=int, default=48,
                    help="tpe total trial budget (study history counts toward it)")
    ap.add_argument("--startup", type=int, default=None,
                    help="tpe random trials before the first model round")
    ap.add_argument("--round-size", type=int, default=8,
                    help="tpe proposals per acquisition round (size --jobs to this)")
    ap.add_argument("--seed", type=int, default=0, help="crs/tpe/random/asha rng seed")
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
                    help="cross-cell transfer from sibling cells in the same "
                         "study: warm = seed candidates from sibling "
                         "incumbents (gsft/crs/tpe), prior = distance-decayed "
                         "Parzen prior over sibling observations (tpe); "
                         "sibling trials never count toward --budget")
    ap.add_argument("--out", type=Path, default=None, help="write best config JSON")
    add_engine_args(ap, Path("results/studies/tune"))
    args = ap.parse_args(argv)

    if args.platform == "wordcount":
        from repro.apps.wordcount import WORDCOUNT_SPACE, make_evaluator

        evaluator = make_evaluator()
        space = WORDCOUNT_SPACE
        platform_key = args.platform
        active = args.active or ["replication", "block_tokens", "num_map_tasks"]
    else:
        arch = get_arch(args.arch)
        shape = SHAPES[args.shape]
        if shape.name in arch.skip_shapes:
            raise SystemExit(f"{args.shape} skipped for {args.arch} (DESIGN.md §6)")
        space = SPACES[args.platform]
        evaluator = RooflineEvaluator(arch, shape, space, chips=args.chips)
        # per-cell (and per-topology) namespace in the shared cache: a
        # different arch/shape/chips must never replay this cell's records
        platform_key = roofline_platform_key(
            args.platform, args.arch, args.shape, args.chips)
        active = args.active or list(space.most_influential)

    budget = None
    if args.algorithm == "gsft":
        kwargs = dict(samples_per_param=args.samples)
    elif args.algorithm == "crs":
        kwargs = dict(m=args.m, k=args.k, max_rounds=args.rounds, seed=args.seed)
    elif args.algorithm == "random":
        budget = args.budget
        kwargs = dict(seed=args.seed)
    elif args.algorithm == "asha":
        # multi-fidelity: --budget caps distinct rung-0 configs; promotions
        # up the rung ladder ride on top of it
        budget = args.budget
        kwargs = dict(inner=args.inner, eta=args.eta,
                      min_fidelity=args.min_fidelity,
                      max_fidelity=args.max_fidelity, seed=args.seed)
    else:  # tpe — warm-starts its observation history from the study on re-runs
        budget = args.budget
        kwargs = dict(n_startup=args.startup, round_size=args.round_size,
                      seed=args.seed)
    # the real platform name namespaces the persistent cache — wordcount
    # records must never alias the roofline "train" platform's
    study = open_persistent_study(args.study, engine_overrides(args))
    with study:
        outcome = study.optimize(
            platform_key,
            args.algorithm,
            evaluator,
            space=space,
            budget=budget,
            active_params=active if args.algorithm == "gsft" else None,
            transfer=args.transfer,
            **kwargs,
        )
    print(json.dumps(outcome.summary(), indent=1, default=str))
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps(outcome.best_config, indent=1, default=str))
        print(f"best config -> {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
