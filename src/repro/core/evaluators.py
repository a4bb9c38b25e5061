"""Evaluator backends for the CMPE — the "run the job, measure time" step.

Three interchangeable implementations of the ``Evaluator`` protocol:

  - ``WalltimeEvaluator`` — actually executes a jitted job on the local
    devices and measures wall-clock time. This is the paper-faithful path
    (their trials ran WordCount on the cluster); used for the WordCount
    reproduction and CPU-sized LM jobs, and it is what you would run
    unchanged on a real v5e pod.
  - ``RooflineEvaluator`` — AOT: builds the (arch × shape) step under the
    candidate config on a tuner-chosen mesh, compiles the loop-free probes,
    and returns the roofline-predicted step time max(compute, memory,
    collective). Infeasible configs (estimated HBM overflow on the target
    chip) are penalized. This is the evaluator for the production-mesh cells
    in this CPU-only container.
  - ``FunctionEvaluator`` — wraps a plain function (unit tests / synthetic
    objectives with known optima).
"""
from __future__ import annotations

import inspect
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

from repro.configs.base import ArchConfig, RunConfig, ShapeConfig
from repro.core import roofline as rl
from repro.core.space import TunableSpace


def _accepts_fidelity(fn: Callable[..., Any]) -> bool:
    """Whether ``fn`` genuinely handles a ``fidelity=`` kwarg.

    A bare ``**kwargs`` does NOT qualify: such a callable would silently
    swallow the kwarg, run the full-size job, and get cached (and ranked by
    ASHA) under a low-fidelity key as if it were the scaled one. Only an
    explicit ``fidelity`` parameter counts — or the opt-in attribute
    ``accepts_fidelity = True`` for wrappers that forward ``**kwargs`` to
    something that really consumes it."""
    if getattr(fn, "accepts_fidelity", False):
        return True
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):  # builtins / C callables
        return False
    for p in sig.parameters.values():
        if p.name == "fidelity" and p.kind in (
            inspect.Parameter.POSITIONAL_OR_KEYWORD,
            inspect.Parameter.KEYWORD_ONLY,
        ):
            return True
    return False


def _block_until_ready(value: Any) -> None:
    """Force JAX async dispatch to finish before the clock is read.

    Jitted jobs return as soon as the work is *enqueued*; timing the bare
    call measures dispatch, not execution. Tolerates ``None`` and arbitrary
    non-array returns (jax.block_until_ready tree-maps leaves and skips
    objects without a ``block_until_ready`` method), and degrades to a no-op
    when jax isn't importable so pure-Python jobs still time fine."""
    if value is None:
        return
    try:
        import jax
    except ImportError:
        return
    jax.block_until_ready(value)


@dataclass
class FunctionEvaluator:
    """Wraps a plain function. Picklable whenever ``fn`` is a module-level
    function — which makes it subprocess-isolatable as-is; for closures and
    lambdas attach an :class:`~repro.core.executors.EvaluatorSpec` via
    ``spec`` instead.

    If ``fn`` accepts a ``fidelity=`` kwarg the evaluator declares
    ``supports_fidelity`` and forwards the rung fraction — the seam the
    synthetic multi-fidelity objectives in the ASHA tests ride on. A plain
    single-argument ``fn`` never sees the kwarg."""

    fn: Callable[[Dict[str, Any]], float]
    spec: Optional[Any] = None  # EvaluatorSpec for subprocess workers
    parallel_safe: bool = True  # wrapped fns are independent pure calls

    def __post_init__(self):
        self.supports_fidelity = _accepts_fidelity(self.fn)

    def __call__(
        self, config: Dict[str, Any], fidelity: float = 1.0
    ) -> Tuple[float, Dict[str, Any]]:
        if fidelity < 1.0 and self.supports_fidelity:
            return float(self.fn(config, fidelity=fidelity)), {}
        return float(self.fn(config)), {}


@dataclass
class WalltimeEvaluator:
    """builder(config) -> zero-arg callable running one full job; we time the
    best of ``repeats`` runs after one warmup (compile) run.

    ``parallel_safe`` is True: the TrialScheduler may fan a batch of these
    over its thread pool (the paper's trials are independent jobs). Beware
    that concurrent trials on one oversubscribed host contend for cores —
    size ``max_workers`` to the machine, as you would cluster slots.

    Fidelity: a sub-fidelity trial measures fewer repeats
    (``max(1, round(repeats × f))`` — measure-step fidelity), and a builder
    that accepts ``fidelity=`` additionally gets the rung fraction to scale
    the job itself (input-scale fidelity — e.g. WordCount on a corpus
    prefix). The measured time is then the low-rung job's real time, which
    is exactly what ASHA ranks within a rung."""

    builder: Callable[[Dict[str, Any]], Callable[[], Any]]
    repeats: int = 3
    parallel_safe: bool = True
    spec: Optional[Any] = None  # EvaluatorSpec — builders are usually closures
    supports_fidelity = True

    def __post_init__(self):
        self._builder_takes_fidelity = _accepts_fidelity(self.builder)

    def __call__(
        self, config: Dict[str, Any], fidelity: float = 1.0
    ) -> Tuple[float, Dict[str, Any]]:
        if fidelity < 1.0 and self._builder_takes_fidelity:
            job = self.builder(config, fidelity=fidelity)
        else:
            job = self.builder(config)
        repeats = self.repeats
        if fidelity < 1.0:
            repeats = max(1, int(round(self.repeats * fidelity)))
        _block_until_ready(job())  # warmup / compile — wait it out too
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            _block_until_ready(job())
            best = min(best, time.perf_counter() - t0)
        info: Dict[str, Any] = {"repeats": repeats}
        if fidelity < 1.0:
            info["fidelity"] = fidelity
        return best, info


@dataclass
class RooflineEvaluator:
    """AOT probe-compile + roofline. ``parallel_safe`` is False — probe
    compilation mutates global XLA state, so the TrialScheduler keeps roofline
    batches serial. Batch speed comes from the **probe-compile memo** instead:
    distinct knob configs that resolve to the same (RunConfig × mesh) — knobs
    the RunConfig doesn't consume, clamped mesh factors — reuse the compiled
    probes and cost nothing beyond a dict lookup."""

    arch: ArchConfig
    shape: ShapeConfig
    space: TunableSpace
    base_run: Optional[RunConfig] = None
    chips: int = 256
    multi_pod: bool = False
    memory_penalty: str = "soft"  # soft | inf
    parallel_safe: bool = False
    spec: Optional[Any] = None  # EvaluatorSpec for subprocess workers
    # probe-depth fidelity: a sub-fidelity call compiles only the single L1
    # probe and extrapolates (skips the L2/M2 probes the affine cost model
    # needs) — roughly 1/2 to 1/3 of the compile cost per fresh config
    supports_fidelity = True

    def __post_init__(self):
        self._probe_memo: Dict[
            Tuple[Any, int, bool], Tuple[float, Dict[str, Any]]
        ] = {}

    def __getstate__(self):
        # subprocess isolation pickles the evaluator into each worker —
        # compiled probes must never cross a process boundary
        state = self.__dict__.copy()
        state["_probe_memo"] = {}
        return state

    def __call__(
        self, config: Dict[str, Any], fidelity: float = 1.0
    ) -> Tuple[float, Dict[str, Any]]:
        run = self.space.to_run_config(config, self.base_run)
        mp = min(int(config.get("mesh_model_parallel", run.mesh_model_parallel)), self.chips)
        run = run.replace(mesh_model_parallel=mp)

        full = fidelity >= 1.0
        # fidelity is part of the memo identity — a cheap single-probe
        # estimate must never replay as the full extrapolation
        memo_key = (run, mp, full)
        hit = self._probe_memo.get(memo_key)
        if hit is not None:
            t, info = hit
            return t, {**info, "probe_compile_reused": True}
        t, info = self._evaluate(run, mp, full)
        self._probe_memo[memo_key] = (t, info)
        return t, info

    def _evaluate(
        self, run: RunConfig, mp: int, full: bool = True
    ) -> Tuple[float, Dict[str, Any]]:
        import jax

        from repro.distributed.steps import make_step
        from repro.launch.mesh import make_tuning_mesh

        mesh = make_tuning_mesh(mp, chips=self.chips, multi_pod=self.multi_pod)

        with jax.set_mesh(mesh):
            per_dev, probe_times = rl.extrapolated_costs(
                self.arch, run, self.shape, mesh, make_step,
                single_probe=not full,
            )
            roof = rl.make_roofline(per_dev, self.arch, self.shape, mesh)
        t = roof.t_step

        est = rl.estimate_tpu_hbm(self.arch, run, self.shape, mesh)
        info: Dict[str, Any] = {**roof.summary(), "hbm_est_gib": est["total_gib"]}
        if not full:
            info["probe_single"] = True  # cheap L1-only extrapolation
        if not est["fits_hbm_16gib"]:
            if self.memory_penalty == "inf":
                return float("inf"), info
            over = est["total_gib"] / (rl.HBM_CAP / 1024**3)
            t = t * (1.0 + over)  # soft penalty steers the search back inside
            info["hbm_penalized"] = True
        return t, info
