#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process is started on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. Its
configuration, traffic mix, limits and per-layer metric readers are files
found by name under ``chipbench/``:

    configs/<config>.json    the model as it is run, with its source and cuts
    traffic/<traffic>.json   the engine, batch, lengths, and what to check
    limits/<cell>.json       the limit of each number that decides ``correct``
    engines/<engine>.py      the loop that drives the program for this traffic
    metrics/<metric>.py      one reader per per-layer metric

Set-up (weights, compile or compile-cache load, warm-up) is timed as
``setup_s``; then work is admitted for ``--seconds`` and the unit in flight
is finished. With ``--trace 1`` a profiler trace is taken over the units the
traffic names and the per-layer metrics are read from it; with ``--trace 0``
the end-to-end metrics are printed. After the window the program's state is
freed and the served tokens (or the first train steps) are compared with a
plain float32 reference under ``chipbench/reference/``.

Exits non-zero, printing no result, without a TPU or with fewer chips than
the cell asks for. The last line of standard output is one JSON object.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "chipbench"
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


@dataclasses.dataclass
class Cell:
    """One workload with everything its files say."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    root: Path = ROOT  # where chipbench/engines and chipbench/metrics are read


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: dict | None = None, root: Path = ROOT) -> Cell:
    bench = bench or json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((root / "chipbench" / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads((root / "chipbench" / "limits" / f"{name}.json").read_text())
    return Cell(
        name=name, chips=w["chips"], config=config, traffic=traffic,
        limits=limits,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root,
    )


def load_module(path: Path):
    """The module of one file under ``chipbench/`` (engines and metric
    readers are found by name, never listed in code), loaded once per path."""
    name = "chipbench_file_" + re.sub(r"\W", "_", str(path.resolve()))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module  # dataclasses look their module up
        spec.loader.exec_module(module)
    return sys.modules[name]


# ----------------------------------------------------------------- plumbing


class CompileClock:
    """Seconds jax spent in backend compilation (a persistent-cache load
    counts as its retrieval time) and programs compiled, since the last
    ``take()``. Copied from ``chip_smoke.py``."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.seconds, self.programs, self.hits = 0.0, 0, 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self):
        out = (self.seconds, self.programs, self.hits)
        self.seconds, self.programs, self.hits = 0.0, 0, 0
        return out


def peak_bytes(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(s.get("peak_bytes_in_use", 0) for s in stats)


def device_problem(devices, chips: int):
    """Why this run cannot go on, or None: a chip is required, never a
    fallback."""
    if devices[0].platform != "tpu":
        return f"no TPU: jax found {devices[0].platform} devices"
    if len(devices) < chips:
        return f"{chips} chip(s) asked for, jax found {len(devices)}"
    return None


def peak_of(kind: str) -> dict:
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in peaks:
        raise SystemExit(f"no peaks for device kind {kind!r} in chipbench/peaks.json")
    return peaks[kind]


# ------------------------------------------------------------------ one run


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader is given."""

    trace: object  # chipbench.trace.Summary of the traced units
    counts: dict  # the engine's analytic counts, summed over the traced units
    peak: dict  # chipbench/peaks.json entry of this device
    compile_s: float  # set-up compile seconds


def run_window(engine, seconds: float, clock, tracer=None, first: int = 0, count: int = 0):
    """Admit units until ``seconds`` have passed, finishing the one in flight.
    With a tracer, units ``first .. first + count - 1`` are traced, and the
    window runs on until they have all run. Returns (seconds of each unit,
    window seconds, trace summary or None)."""
    unit_s, summary = [], None
    t0 = clock()
    while True:
        if tracer is not None and len(unit_s) == first:
            tracer.start()
        t = clock()
        engine.unit()
        unit_s.append(clock() - t)
        if tracer is not None and len(unit_s) == first + count:
            summary, tracer = tracer.stop(), None
        if tracer is None and clock() - t0 >= seconds:
            return unit_s, clock() - t0, summary


class GcPauses:
    """Python garbage-collector pauses while it is on: count and longest."""

    def __init__(self, clock):
        self.clock, self.t, self.count, self.longest = clock, None, 0, 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self.t = self.clock()
        elif self.t is not None:
            self.count += 1
            self.longest = max(self.longest, self.clock() - self.t)

    def stop(self):
        gc.callbacks.remove(self._on)
        return self.count, self.longest


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float = T_PROCESS, clock=time.perf_counter, log=print,
             control: bool = False):
    """Set up, run the window, check, and return the result's fields. With
    ``control`` the check reads the control (the reference in a lower
    precision) in the program's place, and ``correct`` has to come out
    false."""
    import jax

    from chipbench import program
    from chipbench import trace as tracing
    from repro.launch.mesh import make_host_mesh

    log(f"jax ready {clock() - t_start:.3f} s after start")
    compile_clock = CompileClock()
    bench = cell.root / "chipbench"
    engine_mod = load_module(bench / "engines" / f"{cell.traffic['engine']}.py")
    mesh = make_host_mesh(cell.config.get("run", {}).get("mesh_model_parallel", 1),
                          devices=devices[: cell.chips])
    with jax.set_mesh(mesh):
        engine = engine_mod.Engine(cell, seed, mesh, clock)
        setup_s = clock() - t_start
        compile_s, programs, hits = compile_clock.take()
        log(f"set-up {setup_s:.3f} s: {programs} programs compiled or loaded in "
            f"{compile_s:.3f} s, {hits} persistent-cache hits; "
            + ", ".join(f"{k} {v:.3f}" for k, v in engine.setup_phases.items()))

        first, count = cell.traffic["trace"]["first_unit"], cell.traffic["trace"]["units"]
        pauses = GcPauses(clock)
        unit_s, window_s, summary = run_window(
            engine, seconds, clock, tracing.Tracer() if trace else None, first, count)
        gc_count, gc_longest = pauses.stop()
        _, window_programs, _ = compile_clock.take()
        memory = peak_bytes(devices[: cell.chips])
        t = clock()
        footprints = {k: program.footprint_bytes(b) for k, b in engine.bundles.items()}
        log(f"program footprints (bytes, {clock() - t:.3f} s to read): {footprints}")

    slowest = max(range(len(unit_s)), key=unit_s.__getitem__)
    log(f"window {window_s:.3f} s, {len(unit_s)} units of {min(unit_s):.4f} s to "
        f"{unit_s[slowest]:.4f} s (unit {slowest}: "
        + ", ".join(f"{k} {v:.4f}" for k, v in engine.detail(slowest).items())
        + f"), {window_programs} programs compiled inside it; {gc_count} gc pauses, "
        f"longest {gc_longest:.4f} s")
    log("samples: " + ", ".join(f"{k} {v}" for k, v in engine.samples().items()))

    if trace:
        reading = Reading(summary, engine.counts(range(first, first + count)),
                          peak_of(devices[0].device_kind), compile_s)
        metrics = {}
        for m in cell.per_layer:
            value = load_module(bench / "metrics" / f"{m['name']}.py").read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(engine.end_to_end(window_s), setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    attempted, failed = engine.attempted()
    record = engine.release()  # host values only: the device is free again
    del engine
    gc.collect()
    checks = engine_mod.check(cell, seed, record, control=control)
    correct = failed == 0 and all(c["value"] <= c["limit"] for c in checks.values())

    out = {
        "correct": correct,
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": cell.chips,
            # the runtime's peak leaves program temporaries out; the fullest
            # moment is at least the largest step program's footprint
            "memory_peak_bytes": max(memory, *footprints.values()),
            "runtime_peak_bytes": memory,
            "program_bytes": footprints,
        },
    }
    if trace:
        out["device"]["busy_s"] = summary.busy_s
        out["device"]["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    import jax

    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    devices = jax.devices()
    problem = device_problem(devices, cell.chips)
    if problem:
        print(problem, file=sys.stderr)
        return 1
    print(f"device: {devices[0].device_kind} x {len(devices)} "
          f"(platform {devices[0].platform}); cell {cell.name} on {cell.chips}; "
          f"compile cache {cache_dir}", flush=True)

    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
