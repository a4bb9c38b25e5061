#!/usr/bin/env python3
"""``calibrate.py`` for a serving cell, in two calls: serve on the cell's
chips, read on one.

    python3 chipbench/calibrate_served.py serve --workload <cell> --seconds <s> \
        --seeds 1 2 3 --out <dir>
    python3 chipbench/calibrate_served.py read --workload <cell> --records <dir> \
        [--control-seeds 1 2]

``serve`` sets each seed's cell up and serves it for a short window at its
own load, exactly as a run does, and writes the served tokens to
``<dir>/<seed>.json``. ``read`` runs the plain reference over them on the
first chip, as a run's check does after its window, and prints one JSON line
per seed: ``program``, the number a run compares, and for the seeds in
``--control-seeds`` also ``control``, the reference computed in float8 put in
the program's place (``fixed_batch_serve.gaps``).

The reference uses one chip whatever the cell asks for, so a cell on four
chips reads its limits' numbers in a one-chip call. Both exit non-zero
without a TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path[:0] = [str(Path(__file__).resolve().parents[1] / p) for p in ("src", "")]

from chipbench import run  # noqa: E402


def serve(cell, engine_mod, seed, seconds, devices, out: Path, clock=time.perf_counter):
    import jax

    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(cell.config.get("run", {}).get("mesh_model_parallel", 1),
                          devices=devices[: cell.chips])
    with jax.set_mesh(mesh):
        engine = engine_mod.Engine(cell, seed, mesh, clock)
        unit_s, window_s, _ = run.run_window(engine, seconds, clock)
    record = engine.release()
    del engine
    gc.collect()
    served = {str(r): np.asarray(t).tolist() for r, t in record.served.items()}
    (out / f"{seed}.json").write_text(json.dumps({"seed": seed, "served": served}))
    return {"seed": seed, "units": len(unit_s), "window_s": window_s}


def read(cell, engine_mod, path: Path, control: bool, clock=time.perf_counter):
    data = json.loads(path.read_text())
    served = {int(r): np.asarray(t, np.int32) for r, t in data["served"].items()}
    t = clock()
    gaps = engine_mod.gaps(cell, data["seed"], engine_mod.Record(served=served), control)
    return {"seed": data["seed"], "readings_s": clock() - t, **gaps}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("serve", "read"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", type=Path)
    ap.add_argument("--records", type=Path)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    if cell.traffic["engine"] != "fixed_batch_serve":
        raise SystemExit(f"{cell.name} is not a serving cell; use calibrate.py")

    import jax

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    devices = jax.devices()
    problem = run.device_problem(devices, cell.chips if args.mode == "serve" else 1)
    if problem:
        print(problem, file=sys.stderr)
        return 1
    engine_mod = run.load_module(cell.root / "chipbench" / "engines" /
                                 f"{cell.traffic['engine']}.py")
    if args.mode == "serve":
        args.out.mkdir(parents=True, exist_ok=True)
        lines = (serve(cell, engine_mod, s, args.seconds, devices, args.out)
                 for s in args.seeds)
    else:
        lines = (read(cell, engine_mod, p, int(p.stem) in args.control_seeds)
                 for p in sorted(args.records.glob("*.json")))
    for line in lines:
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
