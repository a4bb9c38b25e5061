import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST precede every other import — JAX locks the device
count at first initialization, and the production meshes (16×16 single-pod,
2×16×16 multi-pod) need 512 placeholder host devices.

For each cell this driver:
  1. builds the step bundle (train_step / prefill / decode) with full
     sharding trees,
  2. ``.lower().compile()`` — the pass/fail gate for deliverable (e),
  3. prints ``memory_analysis()`` (fits-in-HBM proof) and ``cost_analysis()``,
  4. extracts collective traffic from the partitioned HLO,
  5. (single-pod) compiles the loop-free reduced-depth probes and writes the
     extrapolated roofline terms (§Roofline),
  6. dumps one JSON artifact per cell under ``results/dryrun/``.

Usage:
  python -m repro.launch.dryrun --all
  python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k --multi-pod
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs.base import SHAPES, RunConfig
from repro.configs.archs import ARCH_NAMES, applicable_shapes, get_arch
from repro.core import roofline as rl
from repro.distributed.steps import make_step
from repro.launch.mesh import make_production_mesh

DEFAULT_OUT = Path("results/dryrun")


def run_cell(
    arch_name: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    run: RunConfig = None,
    mesh=None,
    with_probes: bool = True,
    verbose: bool = True,
) -> dict:
    """Compile one cell and return its artifact dict."""
    arch = get_arch(arch_name)
    shape = SHAPES[shape_name]
    run = run or RunConfig()
    mesh = mesh if mesh is not None else make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    cell = {
        "arch": arch_name,
        "shape": shape_name,
        "mesh": mesh_name,
        "run_config": dataclasses.asdict(run),
        "skipped": False,
    }
    if shape_name in arch.skip_shapes:
        cell["skipped"] = True
        cell["skip_reason"] = "inapplicable shape for this architecture (DESIGN.md §6)"
        return cell

    with jax.set_mesh(mesh):
        t0 = time.time()
        bundle = make_step(arch, run, shape, mesh)
        lowered = bundle.lower()
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        mem = rl.extract_memory(compiled)
        full_costs = rl.extract_costs(compiled)
        if verbose:
            print(f"  memory_analysis: {compiled.memory_analysis()}")
            ca = compiled.cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) else ca
            print(
                "  cost_analysis: flops={:.4g} bytes={:.4g}".format(
                    ca.get("flops", 0.0), ca.get("bytes accessed", 0.0)
                )
            )
        cell.update(
            compile_ok=True,
            t_lower_s=round(t_lower, 2),
            t_compile_s=round(t_compile, 2),
            memory=mem.summary(),
            tpu_hbm_estimate=rl.estimate_tpu_hbm(arch, run, shape, mesh),
            scanned_artifact={
                "flops_per_device": full_costs.flops,
                "bytes_per_device": full_costs.bytes_accessed,
                "collectives": full_costs.collectives.summary(),
                "note": "while-loop bodies counted once (see extrapolated)",
            },
        )

        if with_probes:
            per_dev, probe_times = rl.extrapolated_costs(
                arch, run, shape, mesh, make_step
            )
            roof = rl.make_roofline(per_dev, arch, shape, mesh)
            cell.update(
                extrapolated={
                    "flops_per_device": per_dev.flops,
                    "bytes_per_device": per_dev.bytes_accessed,
                    "collectives": per_dev.collectives.summary(),
                },
                roofline=roof.summary(),
                probe_times=probe_times,
            )
            if verbose:
                s = roof.summary()
                print(
                    "  roofline: compute={t_compute_s:.4g}s memory={t_memory_s:.4g}s "
                    "collective={t_collective_s:.4g}s -> {bottleneck} "
                    "(MFU@step={roofline_fraction_mfu:.3f})".format(**s)
                )
    return cell


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", nargs="*", default=None, choices=list(ARCH_NAMES) + [None])
    ap.add_argument("--shape", nargs="*", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--out", type=Path, default=DEFAULT_OUT)
    args = ap.parse_args()

    archs = args.arch or list(ARCH_NAMES)
    shapes = args.shape or list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for multi_pod in meshes:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mesh_tag = "multi" if multi_pod else "single"
        outdir = args.out / mesh_tag
        outdir.mkdir(parents=True, exist_ok=True)
        for arch_name in archs:
            arch = get_arch(arch_name)
            for shape_name in shapes:
                if shape_name not in SHAPES:
                    continue
                tag = f"{arch_name}__{shape_name} [{mesh_tag}]"
                print(f"=== {tag}")
                try:
                    cell = run_cell(
                        arch_name,
                        shape_name,
                        multi_pod=multi_pod,
                        mesh=mesh,
                        with_probes=not args.no_probes and not multi_pod,
                    )
                except Exception as e:  # noqa: BLE001 — record and continue
                    traceback.print_exc()
                    cell = {
                        "arch": arch_name,
                        "shape": shape_name,
                        "mesh": mesh_tag,
                        "compile_ok": False,
                        "error": f"{type(e).__name__}: {e}",
                    }
                    failures.append(tag)
                path = outdir / f"{arch_name}__{shape_name}.json"
                path.write_text(json.dumps(cell, indent=1, default=float))
                if cell.get("skipped"):
                    print("  SKIPPED (inapplicable)")
    print(f"\nDONE. failures: {failures or 'none'}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
