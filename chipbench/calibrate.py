#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for many seeds in
one process (set-up is long, so one process reads them all).

    python3 chipbench/calibrate.py --workload <cell> --seconds <s> --seeds 1 2 3 ...

For each seed: the cell is set up and served (or trained) for a short window
at its own load, exactly as a run does, and the numbers a run compares are
read. Beside them, on the same seed:

  serving   the control: the reference computed in float8 (e4m3, one scale
            per tensor) put in the program's place, read at the same
            positions of the same prompts and served tokens;
  training  the control (the reference in float8: e4m3 operands forward, e5m2
            cotangents backward, one scale per tensor) and two planted
            faults read through the reference: half of each batch left out
            (the mean over the rest) and every label shifted by one position.
            A state left unchanged reads 1 on the gradient and the change
            numbers by definition, so it needs no run.

One JSON line per seed goes to standard output. Exits non-zero without a
TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1] / p) for p in ("src", "")]

from chipbench import run  # noqa: E402


def serve_readings(engine_mod, cell, seed, record):
    return engine_mod.gaps(cell, seed, record, control=True)


def train_readings(engine_mod, cell, seed, record):
    ref = engine_mod.readings_of(cell, seed)
    raw = {"program": dataclasses.asdict(record),
           "control": engine_mod.control_readings(cell, seed),
           "half_batch": engine_mod.readings_of(cell, seed, half_batch=True),
           "labels_shifted": engine_mod.readings_of(cell, seed, shift_labels=True)}
    out = {k: engine_mod.numbers(r, ref) for k, r in raw.items()}
    out["raw"] = dict(raw, reference=ref)  # per-leaf norms, for choosing what to compare
    return out


def read_seed(cell, seed, seconds, devices, clock=time.perf_counter):
    import jax

    from repro.launch.mesh import make_host_mesh

    engine_mod = run.load_module(cell.root / "chipbench" / "engines" /
                                 f"{cell.traffic['engine']}.py")
    mesh = make_host_mesh(cell.config.get("run", {}).get("mesh_model_parallel", 1),
                          devices=devices[: cell.chips])
    with jax.set_mesh(mesh):
        engine = engine_mod.Engine(cell, seed, mesh, clock)
        unit_s, window_s, _ = run.run_window(engine, seconds, clock)
    record = engine.release()
    del engine
    gc.collect()
    t = clock()
    readings = (serve_readings if cell.traffic["engine"] == "fixed_batch_serve"
                else train_readings)(engine_mod, cell, seed, record)
    return {"seed": seed, "units": len(unit_s), "window_s": window_s,
            "readings_s": clock() - t, **readings}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)

    import jax

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    devices = jax.devices()
    problem = run.device_problem(devices, cell.chips)
    if problem:
        print(problem, file=sys.stderr)
        return 1
    for seed in args.seeds:
        print(json.dumps(read_seed(cell, seed, args.seconds, devices)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
