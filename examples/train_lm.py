"""End-to-end training driver: a ~15M-parameter llama-family model trained
for a few hundred steps on CPU, with checkpointing, failure injection, and
restart — the full production loop at laptop scale.

    PYTHONPATH=src python examples/train_lm.py --steps 200
"""
import argparse
import dataclasses

import jax

from repro.configs.archs import get_arch
from repro.configs.base import RunConfig, ShapeConfig
from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import PipelineConfig, SyntheticLMPipeline
from repro.distributed.steps import init_train_state, make_train_step
from repro.ft.runner import ResilientTrainer, RunnerConfig
from repro.launch.mesh import make_host_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--fail-at", type=int, nargs="*", default=[60])
    args = ap.parse_args()

    # a mid-size smoke model (~15M params): llama family, 4 layers, d=256
    arch = dataclasses.replace(
        get_arch("llama3.2-1b", smoke=True),
        num_layers=4, d_model=256, num_heads=8, num_kv_heads=4, d_ff=1024,
        vocab_size=32768,
    )
    shape = ShapeConfig("train_lm", 128, 8, "train")
    run = RunConfig(mesh_model_parallel=1, learning_rate=1e-3)
    mesh = make_host_mesh(model_parallel=1)

    with jax.set_mesh(mesh):
        bundle = make_train_step(arch, run, shape, mesh)
        state = init_train_state(bundle)
        n = sum(x.size for x in jax.tree.leaves(state["params"]))
        print(f"model: {n/1e6:.1f}M params; {args.steps} steps of "
              f"{shape.global_batch}×{shape.seq_len} tokens")
        trainer = ResilientTrainer(
            step_fn=bundle.jit(),
            state=state,
            pipeline=SyntheticLMPipeline(arch, shape, PipelineConfig(seed=0)),
            ckpt=CheckpointManager(args.ckpt_dir, keep_n=2),
            cfg=RunnerConfig(total_steps=args.steps, checkpoint_every=50),
            fail_at=args.fail_at,
        )
        trainer.run()

    h = trainer.history
    print(f"loss: step0={h[0]['loss']:.4f} -> step{h[-1]['step']}={h[-1]['loss']:.4f} "
          f"(restarts={trainer.restarts})")
    k = max(len(h) // 10, 1)
    for row in h[::k]:
        print(f"  step {row['step']:4d} loss {row['loss']:.4f} ({row['dt']*1e3:.0f} ms)")
    assert h[-1]["loss"] < h[0]["loss"]
    print("OK")


if __name__ == "__main__":
    main()
