"""The two measured platforms for the paper-table benchmarks.

Platform A ("Hadoop" analog): WordCount — the paper's own job, measured wall
time (repro.apps.wordcount).

Platform B ("Spark" analog): a smoke-scale LM training job, measured wall
time. Several of the 12 training knobs bind on CPU (matmul precision, scan
vs. unroll, remat, microbatching); mesh knobs are inert on one device — the
long-tail shape the paper's Table VII also shows.

Both give the CMPE a *measured* ``config → execution time`` function, which
is the paper-faithful experiment; the production-mesh (roofline) tables live
in EXPERIMENTS.md §Dry-run/§Roofline.
"""
from __future__ import annotations

import jax

from repro.apps.wordcount import WORDCOUNT_SPACE, build_wordcount, make_corpus
from repro.configs.archs import get_arch
from repro.configs.base import RunConfig, ShapeConfig
from repro.core.evaluators import WalltimeEvaluator
from repro.core.space import TRAIN_SPACE
from repro.distributed.steps import init_train_state, make_train_step
from repro.launch.mesh import make_host_mesh

LM_ARCH = "llama3.2-1b"
LM_SHAPE = ShapeConfig("bench", 128, 8, "train")
LM_STEPS = 2

# grid knobs for the search tables (kept to 3 axes: 27 + finer cells per run)
LM_ACTIVE = ["matmul_precision", "remat_policy", "microbatch_size"]
WC_ACTIVE = ["replication", "block_tokens", "num_map_tasks"]


def wordcount_evaluator(num_tokens: int = 1 << 21, repeats: int = 2):
    corpus = make_corpus(num_tokens)
    # fidelity-aware builder: ASHA's cheap rungs run a corpus prefix (and
    # WalltimeEvaluator scales the repeat count); full fidelity is unchanged
    return WalltimeEvaluator(
        builder=lambda cfg, fidelity=1.0: build_wordcount(
            cfg, corpus, fidelity=fidelity),
        repeats=repeats,
    ), WORDCOUNT_SPACE


def lm_train_evaluator(repeats: int = 2):
    arch = get_arch(LM_ARCH, smoke=True)
    mesh = make_host_mesh(model_parallel=1)

    def builder(cfg):
        run = TRAIN_SPACE.to_run_config(cfg, RunConfig(mesh_model_parallel=1))
        with jax.set_mesh(mesh):
            bundle = make_train_step(arch, run, LM_SHAPE, mesh)
            state = init_train_state(bundle)
            batch = bundle.model.make_inputs(LM_SHAPE)
            state, batch = bundle.place(mesh, state, batch)
            fn = bundle.jit(donate=False)  # job re-runs from the same state

        def job(state=state):
            with jax.set_mesh(mesh):
                s = state
                for _ in range(LM_STEPS):
                    s, m = fn(s, batch)
                jax.block_until_ready(m["loss"])
            return m

        return job

    return WalltimeEvaluator(builder=builder, repeats=repeats), TRAIN_SPACE
