"""Tune a production-mesh cell with the ROOFLINE evaluator — the tuner
searching the 12-knob training space for qwen2-72b/train_4k on 256 chips
(AOT: every trial is a lower+compile, no execution).

    PYTHONPATH=src python examples/tune_production_cell.py \
        --arch qwen2-72b --shape train_4k --algorithm gsft
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
from pathlib import Path

from repro.configs.archs import ARCH_NAMES, get_arch
from repro.configs.base import SHAPES
from repro.core import SPACES, Study
from repro.core.evaluators import RooflineEvaluator


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-72b", choices=ARCH_NAMES)
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--algorithm", default="gsft", choices=["gsft", "crs"])
    args = ap.parse_args()

    arch = get_arch(args.arch)
    shape = SHAPES[args.shape]
    platform = "train" if shape.kind == "train" else "serve"
    space = SPACES[platform]
    evaluator = RooflineEvaluator(arch, shape, space, chips=256)

    kwargs = (
        dict(active_params=["mesh_model_parallel", "microbatch_size", "remat_policy"],
             samples_per_param=3)
        if args.algorithm == "gsft"
        else dict(m=8, k=3, max_rounds=3)
    )
    study = Study(
        log_path=Path(f"results/examples/tune_{args.arch}_{args.shape}.jsonl"))
    out = study.optimize(platform, args.algorithm, evaluator, **kwargs)
    print(json.dumps(out.summary(), indent=1, default=str))


if __name__ == "__main__":
    main()
