"""Distributed step builders: (arch × shape × mesh × RunConfig) → jit-able
train / prefill / decode steps with full input/output sharding trees.

Every builder returns a ``StepBundle`` carrying the abstract inputs
(ShapeDtypeStructs — no allocation) and the sharding trees, so the same
bundle serves three consumers:

  - the **dry-run** (``bundle.lower(mesh)`` → compile → memory/cost analysis),
  - the **tuner's roofline evaluator** (same artifacts, knobs varied),
  - **real execution** (examples / smoke tests pass concrete arrays).

The paper's knobs enter here: microbatch gradient accumulation
(``microbatch_size``), remat policy (inside the stack scan), ZeRO sharding of
optimizer state, int8 cross-pod gradient compression (partial-manual
``shard_map`` over the ``pod`` axis), and the activation-sharding strategy.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, RunConfig, ShapeConfig
from repro.distributed.sharding import (
    batch_partition_specs,
    make_rules,
    mesh_axis_sizes,
    opt_state_rules,
)
from repro.models.model import Model
from repro.optim import compression
from repro.optim.adamw import (
    AdamWConfig,
    abstract_opt_state,
    adamw_update,
    clip_by_global_norm,
    init_opt_state,
)
from repro.optim.schedules import warmup_cosine


@dataclass
class StepBundle:
    name: str
    fn: Callable
    abstract_inputs: Tuple[Any, ...]
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    donate_argnums: Tuple[int, ...]
    model: Model
    rules: Dict[str, Any]
    mesh: Any = None

    def jit(self, donate: bool = True):
        return jax.jit(
            self.fn,
            in_shardings=self.in_shardings,
            out_shardings=self.out_shardings,
            donate_argnums=self.donate_argnums if donate else (),
        )

    def lower(self):
        return self.jit().lower(*self.abstract_inputs)

    def compile(self):
        return self.lower().compile()

    def place(self, mesh, *args):
        """device_put concrete inputs onto their declared shardings."""
        def put(tree, ps):
            return jax.tree.map(
                lambda x, p: jax.device_put(x, NamedSharding(mesh, p)),
                tree,
                ps,
                is_leaf=lambda x: x is None,
            )

        return tuple(put(a, p) for a, p in zip(args, self.in_shardings))

    def init_params(self, rng):
        """Random parameters made by one jit whose outputs carry the bundle's
        parameter shardings: each device builds only its own shard, so no
        leaf is ever whole on one device."""
        return jax.jit(
            self.model.init_params,
            out_shardings=_named(self.mesh, self.in_shardings[0]),
        )(rng)


def _named(mesh, specs):
    """PartitionSpec tree -> NamedSharding tree on ``mesh``."""
    return jax.tree.map(
        lambda p: NamedSharding(mesh, p), specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def _effective_run(run: RunConfig) -> RunConfig:
    """Resolve derived knobs (matmul precision → compute dtype)."""
    if run.matmul_precision == "f32" and run.compute_dtype != "float32":
        run = run.replace(compute_dtype="float32")
    return run


def _adamw_cfg(run: RunConfig) -> AdamWConfig:
    return AdamWConfig(moment_dtype=run.optimizer_moment_dtype)


# ---------------------------------------------------------------------------
# Train
# ---------------------------------------------------------------------------


def make_train_step(
    arch: ArchConfig, run: RunConfig, shape: ShapeConfig, mesh
) -> StepBundle:
    run = _effective_run(run)
    sizes = mesh_axis_sizes(mesh)
    n_pod = sizes.get("pod", 1)
    compress = run.grad_compression == "int8" and n_pod > 1
    if compress:
        # scatter-free embedding bwd: XLA's scatter partitioner cannot handle
        # the transposed device groups of a partial-manual shard_map region
        run = run.replace(embed_impl="one_hot")
    model = Model(arch, run)
    rules = make_rules(arch, run, shape, mesh)
    opt_rules = opt_state_rules(rules, run)
    cfg = _adamw_cfg(run)

    param_ps = model.param_partition_specs(rules)
    opt_param_ps = model.param_partition_specs(opt_rules)
    batch_ps = batch_partition_specs(arch, shape, mesh, run)

    b = shape.global_batch
    mb = run.microbatch_size or 0
    n_micro = 1
    if mb and mb < b and b % mb == 0:
        n_micro = b // mb

    # ---- rules inside the compression shard_map: the pod axis is manual
    pod_local_shape = dataclasses.replace(shape, global_batch=b // n_pod)
    if compress:
        inner_rules = dict(make_rules(arch, run, pod_local_shape, mesh))
        inner_rules["act_batch"] = (
            ("data",) if (b // n_pod) % sizes.get("data", 1) == 0 else None
        )
        inner_sizes = dict(sizes)
        inner_sizes.pop("pod", None)
        inner_rules["_sizes"] = inner_sizes
    else:
        inner_rules = rules

    def mean_loss(params, batch):
        loss, metrics = model.loss(params, batch, rules=inner_rules)
        return loss, metrics

    grad_fn = jax.value_and_grad(mean_loss, has_aux=True)

    def grads_over_batch(params, batch):
        """Possibly microbatched loss+grad (mean over the whole batch)."""
        if n_micro == 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return loss, metrics, grads

        def reshape(x):
            return x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])

        micro = jax.tree.map(reshape, batch)
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def body(carry, mb_batch):
            acc, loss_acc = carry
            (loss, _), grads = grad_fn(params, mb_batch)
            acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32) / n_micro, acc, grads)
            return (acc, loss_acc + loss / n_micro), None

        (grads, loss), _ = jax.lax.scan(
            body, (zeros, 0.0), micro, unroll=not run.scan_layers
        )
        return loss, {"ce": loss, "aux": jnp.zeros(())}, grads

    def apply_update(state, grads, loss, metrics, new_err=None):
        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, run.gradient_clip)
            lr = warmup_cosine(state["step"], peak_lr=run.learning_rate)
            new_params, new_opt = adamw_update(
                grads, state["opt"], state["params"], state["step"], lr, cfg
            )
        new_state = {
            "params": new_params,
            "opt": new_opt,
            "step": state["step"] + 1,
        }
        if "err" in state:
            new_state["err"] = new_err if new_err is not None else state["err"]
        out_metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr, **metrics}
        return new_state, out_metrics

    if not compress:

        def train_step(state, batch):
            loss, metrics, grads = grads_over_batch(state["params"], batch)
            return apply_update(state, grads, loss, metrics)

    else:
        # Partial-manual shard_map over the pod axis: pod-local grads, int8
        # error-feedback all-reduce across pods, everything else GSPMD.
        def pod_body(params, err, batch):
            loss, metrics, grads = grads_over_batch(params, batch)
            synced, new_err = compression.compress_psum_pod_tree(grads, err)
            n = jax.lax.axis_size("pod")
            loss = jax.lax.psum(loss, "pod") / n
            metrics = jax.tree.map(lambda m: jax.lax.psum(m, "pod") / n, metrics)
            return loss, metrics, synced, new_err

        replicate = lambda tree: jax.tree.map(lambda _: P(), tree)
        # pod-manual in_specs: batch leaves split over pod on dim 0; scalars whole
        pod_batch_specs = {
            k: (P() if v.ndim == 0 else P(*(("pod",) + (None,) * (v.ndim - 1))))
            for k, v in Model(arch, run).input_specs(shape).items()
        }
        metrics_specs = {"ce": P(), "aux": P()}

        def train_step(state, batch):
            params = state["params"]
            body = jax.shard_map(
                pod_body,
                mesh=mesh,
                in_specs=(replicate(params), replicate(state["err"]), pod_batch_specs),
                out_specs=(P(), metrics_specs, replicate(params), replicate(params)),
                axis_names={"pod"},
                check_vma=False,
            )
            loss, metrics, grads, new_err = body(params, state["err"], batch)
            return apply_update(state, grads, loss, metrics, new_err)

    # ---- abstract inputs + shardings
    params_abs = model.abstract_params()
    state_abs = {
        "params": params_abs,
        "opt": abstract_opt_state(params_abs, cfg),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    state_ps = {
        "params": param_ps,
        "opt": {"mu": opt_param_ps, "nu": opt_param_ps},
        "step": P(),
    }
    if compress:
        state_abs["err"] = compression.abstract_error_state(params_abs)
        state_ps["err"] = param_ps
    batch_abs = model.input_specs(shape)

    metrics_ps = {"loss": P(), "grad_norm": P(), "lr": P(), "ce": P(), "aux": P()}
    return StepBundle(
        name=f"train:{arch.name}:{shape.name}",
        fn=train_step,
        abstract_inputs=(state_abs, batch_abs),
        in_shardings=(state_ps, batch_ps),
        out_shardings=(state_ps, metrics_ps),
        donate_argnums=(0,),
        model=model,
        rules=rules,
        mesh=mesh,
    )


def init_train_state(bundle: StepBundle, rng=None):
    """Real initial train state, built by one jit whose outputs carry the
    bundle's state shardings (no leaf is ever materialized whole on one
    device)."""
    model = bundle.model
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    cfg = _adamw_cfg(model.run)
    state_abs, state_ps = bundle.abstract_inputs[0], bundle.in_shardings[0]

    def init(rng):
        params = model.init_params(rng)
        state = {
            "params": params,
            "opt": init_opt_state(params, cfg),
            "step": jnp.zeros((), jnp.int32),
        }
        if "err" in state_abs:
            state["err"] = jax.tree.map(
                lambda a: jnp.zeros(a.shape, a.dtype), state_abs["err"])
        return state

    return jax.jit(init, out_shardings=_named(bundle.mesh, state_ps))(rng)


# ---------------------------------------------------------------------------
# Serve
# ---------------------------------------------------------------------------


def make_prefill_step(
    arch: ArchConfig, run: RunConfig, shape: ShapeConfig, mesh
) -> StepBundle:
    run = _effective_run(run)
    run = run.replace(param_dtype=run.weight_dtype)  # serve: no f32 masters
    model = Model(arch, run)
    rules = make_rules(arch, run, shape, mesh)
    param_ps = model.param_partition_specs(rules)
    batch_ps = batch_partition_specs(arch, shape, mesh, run)
    cache_ps = model.cache_partition_specs(
        rules, shape.global_batch, model.cache_capacity(shape)
    )

    def prefill_step(params, batch):
        return model.prefill(params, batch, rules=rules)

    logits_ps = P(rules["act_batch"], "model")
    return StepBundle(
        name=f"prefill:{arch.name}:{shape.name}",
        fn=prefill_step,
        abstract_inputs=(model.abstract_params(), model.input_specs(shape)),
        in_shardings=(param_ps, batch_ps),
        out_shardings=(logits_ps, cache_ps),
        donate_argnums=(),
        model=model,
        rules=rules,
        mesh=mesh,
    )


def make_decode_step(
    arch: ArchConfig, run: RunConfig, shape: ShapeConfig, mesh
) -> StepBundle:
    run = _effective_run(run)
    run = run.replace(param_dtype=run.weight_dtype)  # serve: no f32 masters
    model = Model(arch, run)
    rules = make_rules(arch, run, shape, mesh)
    param_ps = model.param_partition_specs(rules)
    batch_ps = batch_partition_specs(arch, shape, mesh, run)
    cache_ps = model.cache_partition_specs(
        rules, shape.global_batch, model.cache_capacity(shape)
    )

    def decode_step(params, caches, batch):
        return model.decode_step(params, caches, batch, rules=rules)

    cache_abs = model.cache_abstract(shape.global_batch, model.cache_capacity(shape))
    logits_ps = P(rules["act_batch"], "model")
    return StepBundle(
        name=f"decode:{arch.name}:{shape.name}",
        fn=decode_step,
        abstract_inputs=(model.abstract_params(), cache_abs, model.input_specs(shape)),
        in_shardings=(param_ps, cache_ps, batch_ps),
        out_shardings=(logits_ps, cache_ps),
        donate_argnums=(1,),
        model=model,
        rules=rules,
        mesh=mesh,
    )


def make_step(arch: ArchConfig, run: RunConfig, shape: ShapeConfig, mesh) -> StepBundle:
    if shape.kind == "train":
        return make_train_step(arch, run, shape, mesh)
    if shape.kind == "prefill":
        return make_prefill_step(arch, run, shape, mesh)
    return make_decode_step(arch, run, shape, mesh)
