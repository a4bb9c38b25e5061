"""End-to-end training driver (CPU-runnable with --smoke; pod-ready as-is).

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Wires together: config registry → step bundle (sharded train step) → data
pipeline → checkpoint manager → resilient runner. ``--fail-at`` injects
failures to demo checkpoint/restart; ``--tuned-config`` applies a JSON knob
dict produced by ``repro.launch.tune``.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax

from repro.configs.base import SHAPES, RunConfig, ShapeConfig
from repro.configs.archs import ARCH_NAMES, get_arch
from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import PipelineConfig, SyntheticLMPipeline
from repro.distributed.steps import init_train_state, make_train_step
from repro.ft.runner import ResilientTrainer, RunnerConfig
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[])
    ap.add_argument("--tuned-config", type=Path, default=None)
    args = ap.parse_args(argv)
    use_compile_cache()

    arch = get_arch(args.arch, smoke=args.smoke)
    shape = ShapeConfig("cli_train", args.seq, args.batch, "train")
    run = RunConfig(mesh_model_parallel=args.model_parallel)
    if args.tuned_config:
        from repro.core.space import TRAIN_SPACE

        knobs = json.loads(args.tuned_config.read_text())
        run = TRAIN_SPACE.to_run_config(knobs, run)
    mesh = make_host_mesh(model_parallel=args.model_parallel)

    with jax.set_mesh(mesh):
        bundle = make_train_step(arch, run, shape, mesh)
        state = init_train_state(bundle)
        step_fn = bundle.jit()

        pipeline = SyntheticLMPipeline(
            arch, shape, PipelineConfig(), mesh=mesh,
            batch_sharding=bundle.in_shardings[1],
        )
        ckpt = CheckpointManager(args.ckpt_dir, keep_n=3)
        trainer = ResilientTrainer(
            step_fn=step_fn,
            state=state,
            pipeline=pipeline,
            ckpt=ckpt,
            cfg=RunnerConfig(total_steps=args.steps, checkpoint_every=args.ckpt_every),
            fail_at=args.fail_at,
        )
        t0 = time.time()
        state = trainer.run()
        wall = time.time() - t0

    losses = [h["loss"] for h in trainer.history]
    print(f"trained {args.steps} steps in {wall:.1f}s "
          f"({wall / max(len(trainer.history), 1):.3f}s/step, "
          f"restarts={trainer.restarts}, stragglers={len(trainer.monitor.stragglers)})")
    print(f"loss: first={losses[0]:.4f} last={losses[-1]:.4f}")
    assert losses[-1] < losses[0], "loss did not decrease"
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
