"""Kernel autotuning as a first-class Study workload.

The paper's premise — hand-picked configuration parameters leave execution
time on the table — applies to our own Pallas kernels: ``flash_attention``,
``rwkv6`` and ``ssm_scan`` ship hardcoded block/tile guesses. This module
turns each kernel's knobs into a :class:`~repro.core.space.TunableSpace` and
benchmarks one kernel *variant* per trial with a :class:`KernelEvaluator`:

  - **numerics gate**: every variant's output is checked against the
    shipped pure-jnp oracle (``ref.py``) *before* it is timed; a mismatch
    returns the infeasible penalty, so a fast-but-wrong block configuration
    can never become the incumbent.
  - **fidelity** via scaled repeats (``max(1, round(repeats × f))``), so
    ASHA's cheap rungs time fewer runs of the same variant.
  - **isolation**: ``parallel_safe = False`` — in-process trials share one
    jax runtime and must not race on it. Under ``isolation="subprocess"``
    each worker builds its own evaluator from the attached
    :class:`~repro.core.executors.EvaluatorSpec` (and with
    ``pin_devices=N`` each worker owns one device), so a multi-chip host
    runs N truly concurrent kernel trials.

Cells are keyed ``kernel/<kernel>.<dtype>:<shape-class>`` — one cache
namespace per (kernel, dtype, shape-class) — and :func:`kernel_similarity`
makes shape classes of the *same* kernel+dtype finite-distance siblings, so
the PR 5 transfer priors carry block-size evidence between input scales
(different kernels never exchange evidence: their knobs don't even share
names). Study-tuned incumbents persist to the shipped
``repro/kernels/tuned_table.json`` (:func:`write_tuned_entries`), which the
public kernel entry points consult when the caller passes no explicit block
sizes.
"""
from __future__ import annotations

import json
import math
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

from repro.core.space import IntParam, TunableSpace
from repro.core.transfer import CellKey
from repro.kernels import (
    DEFAULT_TABLE_PATH,
    dtype_token,
    flash_shape_class,
    invalidate_tuned_table_cache,
    rwkv6_shape_class,
    shape_class_distance,
    ssm_shape_class,
    table_key,
)

__all__ = [
    "KERNEL_NAMES",
    "KERNEL_SPACES",
    "DEFAULT_SHAPES",
    "KernelEvaluator",
    "kernel_platform_key",
    "kernel_similarity",
    "make_kernel_evaluator",
    "parse_kernel_platform",
    "shape_class_for",
    "tuned_entry",
    "write_tuned_entries",
]

KERNEL_NAMES = ("flash_attention", "rwkv6", "ssm_scan")

# One TunableSpace per kernel — every knob is a real argument of the public
# entry point, every value the grids can emit is legal after the ops-layer
# snap/clamp (pow2 snapping here, 128-align + clamp-to-sequence there).
KERNEL_SPACES: Dict[str, TunableSpace] = {
    "flash_attention": TunableSpace(
        platform="kernel.flash_attention",
        params=(
            IntParam("block_q", 128, lo=128, hi=1024, pow2=True),
            IntParam("block_kv", 128, lo=128, hi=1024, pow2=True),
        ),
        most_influential=("block_q", "block_kv"),
    ),
    "rwkv6": TunableSpace(
        platform="kernel.rwkv6",
        # hi=64: the chunked factorization carries exp(-cumsum(logw)) per
        # chunk, and float32 overflows once a chunk accumulates ~88 nats of
        # decay — chunks past 64 NaN for typical decay magnitudes (the
        # evaluator's numerics gate would reject them anyway; bounding the
        # space just stops the tuner paying for known-infeasible trials)
        params=(IntParam("chunk", 64, lo=16, hi=64, pow2=True),),
        most_influential=("chunk",),
    ),
    "ssm_scan": TunableSpace(
        platform="kernel.ssm_scan",
        params=(
            IntParam("chunk", 128, lo=16, hi=256, pow2=True),
            IntParam("d_block", 256, lo=16, hi=1024, pow2=True),
        ),
        most_influential=("chunk", "d_block"),
    ),
}

# Shape tuples per kernel (the CLI default sweep):
#   flash_attention: (B, S, Hq, Hkv, Dh)
#   rwkv6:           (B, S, H, Hd)
#   ssm_scan:        (B, S, Di, N)
DEFAULT_SHAPES: Dict[str, Tuple[Tuple[int, ...], ...]] = {
    "flash_attention": ((2, 256, 4, 2, 64), (1, 512, 4, 2, 64)),
    "rwkv6": ((2, 160, 3, 32), (1, 256, 2, 64)),
    "ssm_scan": ((2, 128, 64, 8), (1, 256, 64, 16)),
}

_SHAPE_RANK = {"flash_attention": 5, "rwkv6": 4, "ssm_scan": 4}

# default relative-error gates per dtype (the parity tests' thresholds with
# headroom for accumulated rounding at large blocks)
_DEFAULT_TOL = {"f32": 1e-4, "bf16": 3e-2, "f16": 3e-2}


def shape_class_for(kernel: str, shape: Tuple[int, ...]) -> str:
    """The compact dims string a shape tuple belongs to (see
    ``repro.kernels``)."""
    if kernel == "flash_attention":
        b, s, hq, hkv, dh = shape
        return flash_shape_class((b, s, hq, dh), (b, s, hkv, dh))
    if kernel == "rwkv6":
        return rwkv6_shape_class(shape)
    if kernel == "ssm_scan":
        b, s, di, n = shape
        return ssm_shape_class((b, s, di), n)
    raise ValueError(f"unknown kernel {kernel!r} (one of {KERNEL_NAMES})")


def kernel_platform_key(kernel: str, dtype: Any, shape_class: str) -> str:
    """Cache namespace for one kernel cell:
    ``kernel/<kernel>.<dtype>:<shape-class>``."""
    return f"kernel/{kernel}.{dtype_token(dtype)}:{shape_class}"


def parse_kernel_platform(platform: str) -> Tuple[str, str, str]:
    """Inverse of :func:`kernel_platform_key` → (kernel, dtype, shape_class)."""
    base, _, cell = platform.partition("/")
    if base != "kernel" or ":" not in cell:
        raise ValueError(f"not a kernel cell namespace: {platform!r}")
    arch, _, shape_class = cell.partition(":")
    kernel, _, dtype = arch.rpartition(".")
    if kernel not in KERNEL_NAMES:
        raise ValueError(f"unknown kernel in namespace {platform!r}")
    return kernel, dtype, shape_class


def kernel_similarity(a: CellKey, b: CellKey) -> float:
    """Sibling distance for kernel cells: ``inf`` across different kernels
    or dtypes (their knob sets / numerics aren't comparable evidence),
    summed |log2| dim distance between shape classes otherwise — a 256-token
    sweep informs the 512-token cell at weight exp(-1)."""
    if a.base != b.base or a.arch != b.arch:
        return math.inf
    if a.shape is None or b.shape is None:
        return 0.5 if a.shape == b.shape else math.inf
    return shape_class_distance(a.shape, b.shape)


# ---------------------------------------------------------------- evaluator


@dataclass
class KernelEvaluator:
    """Benchmark one Pallas-kernel variant per trial.

    ``__call__(config)`` builds the kernel entry point with the trial's
    block knobs, runs it once (compile + **numerics gate** against the
    ``ref.py`` oracle — mismatch ⇒ infeasible penalty before any timing),
    then times ``repeats`` runs under ``jax.block_until_ready`` and returns
    the best.

    Inputs and the oracle output are generated once per evaluator (seeded)
    and reused across trials, so every variant is measured on identical
    data. Kernels are compiled for the default device; ``interpret=True``
    is the explicit opt-in that runs their bodies in the Pallas interpreter
    (CPU tests) — never a speed measurement.
    """

    kernel: str
    shape: Tuple[int, ...]
    dtype: str = "f32"
    repeats: int = 5
    interpret: bool = False
    tolerance: Optional[float] = None
    seed: int = 0
    spec: Optional[Any] = None  # EvaluatorSpec for subprocess workers
    # one jax runtime per process: in-process trials must not race on it —
    # subprocess isolation (one runtime per worker) is the parallel path
    parallel_safe = False
    supports_fidelity = True  # scaled repeats

    INFEASIBLE = float("inf")

    def __post_init__(self):
        if self.kernel not in KERNEL_NAMES:
            raise ValueError(
                f"unknown kernel {self.kernel!r} (one of {KERNEL_NAMES})"
            )
        self.shape = tuple(int(d) for d in self.shape)
        want = _SHAPE_RANK[self.kernel]
        if len(self.shape) != want:
            raise ValueError(
                f"{self.kernel} shapes have {want} dims "
                f"({'B,S,Hq,Hkv,Dh' if want == 5 else 'see DEFAULT_SHAPES'}), "
                f"got {self.shape}"
            )
        if self.tolerance is None:
            self.tolerance = _DEFAULT_TOL.get(self.dtype, 1e-4)
        self._data: Optional[Tuple[Any, ...]] = None  # inputs + oracle output

    def __getstate__(self):
        # device arrays must never cross a process boundary; workers rebuild
        state = self.__dict__.copy()
        state["_data"] = None
        return state

    # -- identity helpers

    def shape_class(self) -> str:
        return shape_class_for(self.kernel, self.shape)

    def platform_key(self) -> str:
        return kernel_platform_key(self.kernel, self.dtype, self.shape_class())

    # -- data / variant construction

    def _jnp_dtype(self):
        import jax.numpy as jnp

        return {
            "f32": jnp.float32, "bf16": jnp.bfloat16, "f16": jnp.float16,
        }.get(self.dtype, jnp.float32)

    def _materialize(self) -> Tuple[Any, ...]:
        """(inputs..., oracle output) — generated once, reused per trial."""
        if self._data is not None:
            return self._data
        import jax
        import jax.numpy as jnp

        dt = self._jnp_dtype()
        key = jax.random.PRNGKey(self.seed)
        if self.kernel == "flash_attention":
            from repro.kernels.flash_attention.ref import attention_ref

            b, s, hq, hkv, dh = self.shape
            ks = jax.random.split(key, 3)
            # q pre-scaled, scale=1.0 everywhere (the model's convention)
            q = (jax.random.normal(ks[0], (b, s, hq, dh), dt) * dh**-0.5)
            k = jax.random.normal(ks[1], (b, s, hkv, dh), dt)
            v = jax.random.normal(ks[2], (b, s, hkv, dh), dt)
            ref = attention_ref(q, k, v, causal=True, scale=1.0)
            data = (q, k, v, ref)
        elif self.kernel == "rwkv6":
            from repro.kernels.rwkv6.ref import wkv6_ref

            b, s, h, hd = self.shape
            ks = jax.random.split(key, 5)
            r, k, v = (
                0.5 * jax.random.normal(ks[i], (b, s, h, hd), dt)
                for i in range(3)
            )
            logw = -jnp.exp(0.3 * jax.random.normal(ks[3], (b, s, h, hd), dt))
            u = 0.3 * jax.random.normal(ks[4], (h, hd), dt)
            ref = wkv6_ref(r, k, v, logw, u)
            data = (r, k, v, logw, u, ref)
        else:  # ssm_scan
            from repro.kernels.ssm_scan.ref import ssm_scan_ref

            b, s, di, n = self.shape
            ks = jax.random.split(key, 5)
            dt_in = jax.nn.softplus(jax.random.normal(ks[0], (b, s, di), dt))
            u = jax.random.normal(ks[1], (b, s, di), dt)
            bt = jax.random.normal(ks[2], (b, s, n), dt)
            ct = jax.random.normal(ks[3], (b, s, n), dt)
            a = -jnp.exp(0.3 * jax.random.normal(ks[4], (di, n), dt))
            ref = ssm_scan_ref(dt_in, u, bt, ct, a)
            data = (dt_in, u, bt, ct, a, ref)
        self._data = tuple(jax.block_until_ready(x) for x in data)
        return self._data

    def _variant(self, config: Dict[str, Any]):
        """(zero-arg jitted job, oracle output) for one knob config."""
        import functools

        import jax

        data = self._materialize()
        if self.kernel == "flash_attention":
            from repro.kernels.flash_attention.ops import flash_attention

            q, k, v, ref = data
            fn = jax.jit(functools.partial(
                flash_attention,
                causal=True, scale=1.0,
                block_q=int(config["block_q"]),
                block_kv=int(config["block_kv"]),
                interpret=self.interpret,
            ))
            return (lambda: fn(q, k, v)), ref
        if self.kernel == "rwkv6":
            from repro.kernels.rwkv6.ops import wkv6

            r, k, v, logw, u, ref = data
            fn = jax.jit(functools.partial(
                wkv6, chunk=int(config["chunk"]), interpret=self.interpret,
            ))
            return (lambda: fn(r, k, v, logw, u)), ref
        from repro.kernels.ssm_scan.ops import selective_scan

        dt_in, u, bt, ct, a, ref = data
        fn = jax.jit(functools.partial(
            selective_scan,
            chunk=int(config["chunk"]), d_block=int(config["d_block"]),
            interpret=self.interpret,
        ))
        return (lambda: fn(dt_in, u, bt, ct, a)), ref

    # -- the evaluator protocol

    def __call__(
        self, config: Dict[str, Any], fidelity: float = 1.0
    ) -> Tuple[float, Dict[str, Any]]:
        import jax
        import jax.numpy as jnp

        job, ref = self._variant(config)
        out = jax.block_until_ready(job())  # compile + warmup

        # numerics gate BEFORE timing: a wrong variant must never be ranked
        out32 = out.astype(jnp.float32)
        ref32 = ref.astype(jnp.float32)
        rel = float(
            jnp.max(jnp.abs(out32 - ref32)) / (jnp.max(jnp.abs(ref32)) + 1e-9)
        )
        info: Dict[str, Any] = {
            "kernel": self.kernel,
            "shape_class": self.shape_class(),
            "max_rel_err": rel,
        }
        if not math.isfinite(rel) or rel > self.tolerance:
            info["numerics_mismatch"] = True
            info["tolerance"] = self.tolerance
            return self.INFEASIBLE, info

        repeats = self.repeats
        if fidelity < 1.0:
            repeats = max(1, int(round(self.repeats * fidelity)))
            info["fidelity"] = fidelity
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(job())
            best = min(best, time.perf_counter() - t0)
        info["repeats"] = repeats
        return best, info


def make_kernel_evaluator(
    kernel: str,
    shape: Tuple[int, ...],
    dtype: str = "f32",
    *,
    repeats: int = 5,
    interpret: bool = False,
    tolerance: Optional[float] = None,
    seed: int = 0,
) -> KernelEvaluator:
    """Module-level factory (the dotted-path target subprocess workers
    resolve), with the matching :class:`EvaluatorSpec` pre-attached."""
    from repro.core.executors import EvaluatorSpec

    ev = KernelEvaluator(
        kernel, tuple(int(d) for d in shape), dtype,
        repeats=repeats, interpret=interpret, tolerance=tolerance, seed=seed,
    )
    ev.spec = EvaluatorSpec.factory(
        "repro.core.kernel_tune:make_kernel_evaluator",
        kernel, tuple(int(d) for d in shape), dtype,
        repeats=repeats, interpret=interpret, tolerance=tolerance, seed=seed,
    )
    return ev


# -------------------------------------------------------------- tuned table


def write_tuned_entries(
    entries: Dict[str, Dict[str, Any]],
    path: Optional[Path] = None,
) -> Path:
    """Merge ``{table_key: {"config": .., "time_s": .., "source": ..}}``
    into the tuned table (creating it if absent) and invalidate the loader
    cache so the very next kernel call sees the new incumbents."""
    p = Path(path) if path is not None else DEFAULT_TABLE_PATH
    existing: Dict[str, Any] = {}
    if p.exists():
        try:
            raw = json.loads(p.read_text())
            if isinstance(raw, dict) and isinstance(raw.get("entries"), dict):
                existing = raw["entries"]
        except (ValueError, OSError):
            existing = {}  # a corrupt table is replaced wholesale
    existing.update(entries)
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(
        {"version": 1, "entries": dict(sorted(existing.items()))}, indent=1,
    ) + "\n")
    invalidate_tuned_table_cache()
    return p


def tuned_entry(
    kernel: str, dtype: str, shape_class: str,
    config: Dict[str, Any], time_s: float, source: str,
) -> Dict[str, Dict[str, Any]]:
    """One table entry, keyed for :func:`write_tuned_entries`."""
    space = KERNEL_SPACES[kernel]
    known = set(space.names())
    return {
        table_key(kernel, dtype, shape_class): {
            "config": {k: v for k, v in config.items() if k in known},
            "time_s": float(time_s),
            "source": source,
        }
    }
