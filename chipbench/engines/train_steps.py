"""Training steps back to back, as ``repro.launch.train`` wires them: the
program's ``make_train_step`` bundle ``.jit()``, a train state, and the
program's ``SyntheticLMPipeline`` seeded with the run's seed, its prefetch
thread running throughout. Each step waits on its metrics, as
``ResilientTrainer`` does; no checkpoint is saved.

Set-up builds the one compiled step and its state and drives it through the
first steps (the first compiles) with the window's own call and feed; the
window then continues from that same state. The state starts from weights
drawn by ``chipbench.gen`` (float32 masters, zero moments). What the check
compares is read on the device after those steps: each step's loss, every
leaf's norm of the first clipped gradient (the first moment after one step,
divided by 1 - b1), and every leaf's norm of its change over the steps.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops, gen, program
from chipbench.reference import dense_lm, whisper
from chipbench.trace import span
from repro.configs.base import RunConfig, ShapeConfig
from repro.data.pipeline import PipelineConfig, SyntheticLMPipeline
from repro.distributed.steps import make_train_step

@jax.jit
def _leaf_norms(tree):
    return {gen.path_str(p): jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


@dataclasses.dataclass
class Record:
    losses: list
    grad_norms: dict
    change_norms: dict


class Engine:
    def __init__(self, cell, seed: int, mesh, clock):
        cfg, tr = cell.config, cell.traffic
        self.cfg, self.clock = cfg, clock
        self.B, self.S = tr["batch"], tr["target_len"]
        arch = program.arch(cfg)
        run = RunConfig(**cfg.get("run", {}))
        shape = ShapeConfig("bench_train", self.S, self.B, "train")
        bundle = make_train_step(arch, run, shape, mesh)
        self.bundles = {"train": bundle}
        self.step_fn = bundle.jit()
        key = gen.seed_key(seed)
        state_abs = bundle.abstract_inputs[0]

        def init(key):
            return {
                "params": gen.tree(key, state_abs["params"]),
                "opt": jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), state_abs["opt"]),
                "step": jnp.zeros((), jnp.int32),
            }

        t = clock()
        self.state = jax.block_until_ready(
            jax.jit(init, out_shardings=program.named(mesh, bundle.in_shardings[0]))(key))
        self.setup_phases = {"weights_s": clock() - t}
        t = clock()
        self.pipeline = SyntheticLMPipeline(
            arch, shape, PipelineConfig(seed=seed), mesh=mesh,
            batch_sharding=bundle.in_shardings[1])
        self.batches = iter(self.pipeline)
        self.steps, self.wait, self.step_s, self.unit_counts = 0, [], [], []

        # the first steps, through the window's own call and feed
        losses, grad_norms = [], None
        b1 = cfg["optimizer"]["b1"]
        for s in range(tr["check_steps"]):
            self.state, metrics = self.step_fn(self.state, next(self.batches))
            losses.append(float(metrics["loss"]))
            if s == 0:
                grad_norms = {k: float(v) / (1.0 - b1)
                              for k, v in _leaf_norms(self.state["opt"]["mu"]).items()}
        p0 = jax.jit(lambda k: gen.tree(k, state_abs["params"]))(key)
        change = _leaf_norms(jax.tree.map(jnp.subtract, self.state["params"], p0))
        self.record = Record(losses, grad_norms, {k: float(v) for k, v in change.items()})
        del p0
        self.setup_phases["first_steps_s"] = clock() - t

    def unit(self):
        """One step: its batch from the pipeline, the step, its metrics."""
        with span("unit"):
            t = self.clock()
            with span("next_batch"):
                batch = next(self.batches)
            self.wait.append(self.clock() - t)
            t = self.clock()
            with span("train_step"):
                self.state, metrics = self.step_fn(self.state, batch)
                jax.block_until_ready(metrics)
            self.step_s.append(self.clock() - t)
        self.steps += 1
        self.unit_counts.append({"train_steps": 1,
                                 "train_flops": flops.train_flops(self.cfg, self.B, self.S)})

    def end_to_end(self, window_s: float) -> dict:
        return {"train_tok_s": self.steps * self.B * self.S / window_s}

    def samples(self) -> dict:
        return {"train_steps": self.steps,
                "input_wait_mean_ms": round(1e3 * float(np.mean(self.wait)), 3)}

    def counts(self, units) -> dict:
        return program.sum_counts(self.unit_counts, units)

    def detail(self, unit: int) -> dict:
        return {"input_wait_s": self.wait[unit], "step_s": self.step_s[unit]}

    def attempted(self):
        return self.steps, 0

    def release(self) -> Record:
        self.batches.close()  # stops the pipeline's prefetch thread
        self.state = self.step_fn = self.batches = self.pipeline = self.bundles = None
        return self.record


def readings_of(cell, seed: int, quant=whisper.identity, grad_quant=whisper.identity,
                half_batch: bool = False, shift_labels: bool = False) -> dict:
    """The reference's readings over the batches the program's first steps
    trained on. Planted faults: ``half_batch`` reads over the first half of
    each batch's rows, ``shift_labels`` with every label moved one position."""
    cfg, tr = cell.config, cell.traffic
    batches = [gen.train_batch(seed, s, tr["batch"], tr["target_len"],
                               cfg["max_source_positions"], cfg["hidden_size"],
                               cfg["vocab_size"])
               for s in range(tr["check_steps"])]
    if half_batch:
        batches = [{k: v[: tr["batch"] // 2] for k, v in b.items()} for b in batches]
    if shift_labels:
        batches = [dict(b, labels=np.roll(b["labels"], 1, axis=1)) for b in batches]
    return whisper.readings(cfg, gen.seed_key(seed), batches, quant=quant,
                            grad_quant=grad_quant, rows_per_block=tr["check_rows_per_block"])


def numbers(prog: dict, ref: dict) -> dict:
    """Every number the check can compare, the program's readings against
    the reference's: the widest loss gap over the steps; by leaf, the gap of
    the first clipped gradient's norm and of the change's norm, the worst
    leaf and the median leaf. Leaves whose reference gradient is under a
    thousandth of the median leaf's move by rounding alone and are left out
    of the change."""
    g = ref["grad_norms"]
    med = float(np.median(list(g.values())))
    moved = [k for k, v in g.items() if v >= 1e-3 * med]
    grad = whisper.leaf_gaps(prog["grad_norms"], g, list(g))
    change = whisper.leaf_gaps(prog["change_norms"], ref["change_norms"], moved)
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"])),
        "grad_leaf_gap": max(grad), "grad_median_gap": float(np.median(grad)),
        "change_leaf_gap": max(change), "change_median_gap": float(np.median(change)),
    }


def control_readings(cell, seed: int) -> dict:
    """The control: the reference as fp8 training runs it (e4m3 operands
    forward, e5m2 cotangents backward, one scale per tensor)."""
    return readings_of(cell, seed, quant=whisper.straight_through(dense_lm.fp8),
                       grad_quant=whisper.fp8_cotangent)


def check(cell, seed: int, record: Record, control: bool = False) -> dict:
    """The numbers the cell's limits file names, each beside its limit;
    with ``control``, the control's readings stand in for the program's."""
    prog = control_readings(cell, seed) if control else dataclasses.asdict(record)
    values = numbers(prog, readings_of(cell, seed))
    return {k: {"value": values[k], "limit": lim} for k, lim in cell.limits.items()}
