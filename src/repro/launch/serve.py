"""Batched serving driver: prefill a request batch, then greedy decode —
optionally under the online safety-bounded tuner.

Offline (one measured serve of one config):

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --smoke \
        --batch 4 --prompt-len 32 --max-new 16

The prefill bundle fills the KV/state caches (capacity = prompt + max-new),
the decode bundle is stepped token-by-token with donated caches. Compilation
happens in an untimed warmup pass, so the reported numbers are execution
latency, and the decode loop reports per-window p50/p99 through
:class:`repro.serving.metrics.DecodeWindowMonitor` rather than one aggregate.
``--tuned-config`` applies a knob dict from the tuner (snapped into
SERVE_SPACE first — a hand-edited or stale dict lands on the space's grid
instead of silently running an off-space config).

Online (--online-tune): the decode path runs under the
:class:`repro.serving.controller.OnlineController` — the baseline config
always serves the majority of decode windows, one strategy-proposed candidate
at a time serves a probation slice inside a p99 safety envelope, and every
guard decision is journaled into the --study directory:

    PYTHONPATH=src python -m repro.launch.serve --online-tune \
        --study results/studies/online --traffic drift --strategy tpe

``--traffic flat|regression|drift`` drives the scripted synthetic traffic
generator (phase shifts, injected regressions — see repro.serving.traffic);
``--traffic real`` serves measured decode windows on real arrays. A re-run
against the same study resumes from the surviving baseline.
"""
from __future__ import annotations

import argparse
import json
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any

from repro.configs.archs import ARCH_NAMES
from repro.launch.compile_cache import use_compile_cache

ONLINE_TRACES = ("flat", "regression", "drift")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--tuned-config", type=Path, default=None)
    ap.add_argument("--window-steps", type=int, default=8,
                    help="decode steps per metrics window (p50/p99 reported "
                         "per window)")
    online = ap.add_argument_group("online tuning (--online-tune)")
    online.add_argument("--online-tune", action="store_true",
                        help="run the decode path under the safety-bounded "
                             "online controller (requires --study)")
    online.add_argument("--study", type=Path, default=None,
                        help="Study directory receiving the online session's "
                             "journal (guard decisions, window records); a "
                             "re-run resumes from the surviving baseline")
    online.add_argument("--traffic", default="drift",
                        choices=("real",) + ONLINE_TRACES,
                        help="scripted synthetic trace, or 'real' to serve "
                             "measured decode windows on real arrays")
    online.add_argument("--windows", type=int, default=None,
                        help="decode windows to serve (default: the scripted "
                             "trace length, or 12 for real traffic)")
    online.add_argument("--strategy", default="tpe",
                        choices=["tpe", "random", "crs"],
                        help="ask/tell strategy proposing candidates")
    online.add_argument("--budget", type=int, default=32,
                        help="strategy observation budget (tpe/random)")
    online.add_argument("--seed", type=int, default=0,
                        help="strategy + synthetic-traffic rng seed")
    online.add_argument("--slice-frac", type=float, default=0.2,
                        help="fraction of windows the candidate may serve "
                             "(must stay < 0.5: baseline keeps the majority)")
    online.add_argument("--safety-p99", type=float, default=1.25,
                        help="rollback bound: candidate p99 above this "
                             "multiple of the baseline p99 rolls back")
    online.add_argument("--probation", type=int, default=3,
                        help="candidate windows before promote/demote")
    online.add_argument("--promote-margin", type=float, default=0.03,
                        help="fractional p99 improvement required to promote")
    online.add_argument("--warmup-windows", type=int, default=2,
                        help="baseline-only windows before the first candidate")
    online.add_argument("--prefilter", default="static",
                        choices=["off", "static"],
                        help="static feasibility vet on proposals before they "
                             "serve traffic (default static)")
    return ap


def load_tuned_config(path: Path) -> dict:
    """A --tuned-config dict snapped onto SERVE_SPACE's grid: out-of-bounds
    or off-grid values (hand edits, stale files from an older space) land on
    the nearest legal point instead of reaching the run config raw."""
    from repro.core.space import SERVE_SPACE
    from repro.core.transfer import snap_into_space

    return snap_into_space(SERVE_SPACE, json.loads(Path(path).read_text()))


def main(argv=None):
    args = build_parser().parse_args(argv)
    use_compile_cache()
    if args.online_tune:
        if args.study is None:
            raise SystemExit("--online-tune requires --study DIR")
        return run_online(args)
    return run_offline(args)


# --------------------------------------------------------------- offline path


def grow_caches(caches, extra: int):
    """Grow prefill caches (capacity = prompt) by ``extra`` decode slots."""
    import jax
    import jax.numpy as jnp

    def grow(path, x):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        if name in ("k", "v", "ks", "vs"):
            pad = [(0, 0)] * x.ndim
            pad[2] = (0, extra)
            return jnp.pad(x, pad)
        return x

    return jax.tree_util.tree_map_with_path(grow, caches)


@dataclass
class ServeRun:
    """What one measured serve produced. ``prefill_logits`` /
    ``decode_logits`` are the timed prefill's and first timed decode step's
    (B, V) logits, kept with the sharded ``params`` and the prompt ``batch``
    so a caller can replay both steps elsewhere and compare."""

    t_prefill: float
    t_decode: float
    tokens: Any  # (B, max_new) generated ids
    params: Any
    batch: Any
    prefill_logits: Any
    decode_logits: Any


def _measured_serve(run, args, monitor) -> ServeRun:
    """One full serve of ``run``: compile + warm up untimed, then measure
    prefill latency and per-step decode latencies into ``monitor`` (one
    metrics window per --window-steps decode steps)."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import ShapeConfig
    from repro.configs.archs import get_arch
    from repro.distributed.steps import make_decode_step, make_prefill_step
    from repro.launch.mesh import make_host_mesh

    arch = get_arch(args.arch, smoke=args.smoke)
    total = args.prompt_len + args.max_new
    prefill_shape = ShapeConfig("cli_prefill", args.prompt_len, args.batch, "prefill")
    decode_shape = ShapeConfig("cli_decode", total, args.batch, "decode")
    mesh = make_host_mesh(model_parallel=run.mesh_model_parallel)

    with jax.set_mesh(mesh):
        pre = make_prefill_step(arch, run, prefill_shape, mesh)
        dec = make_decode_step(arch, run, decode_shape, mesh)
        model = pre.model
        params = pre.init_params(jax.random.PRNGKey(0))
        batch = model.make_inputs(prefill_shape)

        prefill_fn = pre.jit()
        decode_fn = dec.jit()

        def prefilled():
            logits, caches = jax.block_until_ready(prefill_fn(params, batch))
            return logits, grow_caches(caches, args.max_new)

        # untimed warmup: the first prefill_fn/decode_fn calls compile, which
        # must not land inside the timed loop. The decode step donates its
        # caches, so the warmup step consumes this prefill's output — the
        # timed run below re-prefills (now compiled) for fresh caches.
        logits, caches = prefilled()
        warm_tokens = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        jax.block_until_ready(decode_fn(params, caches, {
            "tokens": warm_tokens,
            "cache_len": jnp.asarray(args.prompt_len, jnp.int32),
        }))

        t0 = time.perf_counter()
        logits, caches = prefilled()
        t_prefill = time.perf_counter() - t0

        prefill_logits = logits
        decode_logits = None
        tokens = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        generated = [tokens]
        steps = args.max_new - 1
        in_window = 0
        t0 = time.perf_counter()
        for i in range(steps):
            if in_window == 0:
                monitor.begin_window()
            step_batch = {
                "tokens": tokens,
                "cache_len": jnp.asarray(args.prompt_len + i, jnp.int32),
            }
            t_step = time.perf_counter()
            logits, caches = decode_fn(params, caches, step_batch)
            tokens = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            jax.block_until_ready(tokens)
            monitor.record(time.perf_counter() - t_step, tokens=args.batch)
            if decode_logits is None:
                decode_logits = logits
            generated.append(tokens)
            in_window += 1
            if in_window >= args.window_steps:
                monitor.end_window()
                in_window = 0
        t_decode = time.perf_counter() - t0
        if in_window:
            monitor.end_window()

    out = jnp.concatenate(generated, axis=1)
    return ServeRun(t_prefill, t_decode, out, params, batch,
                    prefill_logits, decode_logits)


def run_offline(args) -> int:
    from repro.configs.base import RunConfig
    from repro.serving.metrics import DecodeWindowMonitor

    run = RunConfig(mesh_model_parallel=args.model_parallel)
    if args.tuned_config:
        from repro.core.space import SERVE_SPACE

        tuned = load_tuned_config(args.tuned_config)
        # the host topology is a fact of this machine, not a knob a config
        # file may override — --model-parallel always wins
        tuned["mesh_model_parallel"] = args.model_parallel
        run = SERVE_SPACE.to_run_config(tuned, run)

    monitor = DecodeWindowMonitor(clock=time.perf_counter)
    served = _measured_serve(run, args, monitor)
    t_prefill, t_decode, out = served.t_prefill, served.t_decode, served.tokens

    n_new = args.max_new * args.batch
    print(f"prefill: {args.batch}×{args.prompt_len} tokens in {t_prefill:.3f}s")
    print(f"decode : {n_new} tokens in {t_decode:.3f}s "
          f"({n_new / max(t_decode, 1e-9):.1f} tok/s)")
    for w in monitor.history:
        print(f"  window {w.window}: {w.count} steps  "
              f"p50 {w.p50 * 1e3:.2f}ms  p99 {w.p99 * 1e3:.2f}ms  "
              f"{w.tokens_per_s:.1f} tok/s")
    agg = monitor.aggregate()
    if agg is not None:
        print(f"decode p50 {agg.p50 * 1e3:.2f}ms  p99 {agg.p99 * 1e3:.2f}ms "
              f"over {len(monitor.history)} windows")
    print("sampled token ids (first request):", out[0].tolist())
    return 0


# ---------------------------------------------------------------- online path


def online_platform_key(args) -> str:
    """Cache/journal namespace for an online session. Synthetic traces get
    their own namespace per trace (a 'drift' journal must not seed a
    'regression' run's baseline); real traffic namespaces by arch."""
    if args.traffic == "real":
        return f"serve-online/{args.arch}"
    return f"serve-online/{args.traffic}"


def make_online_strategy(args, space, fixed=None):
    from repro.core.strategies import make_strategy

    if args.strategy == "tpe":
        # round_size=1: the controller asks for one candidate at a time
        kwargs = dict(max_trials=args.budget, round_size=1, seed=args.seed)
    elif args.strategy == "random":
        kwargs = dict(max_trials=args.budget, seed=args.seed)
    else:  # crs
        kwargs = dict(seed=args.seed)
    return make_strategy(args.strategy, space, fixed=fixed, **kwargs)


def _serve_windows_synthetic(args, controller, windows):
    """Scripted traffic: latencies come from the deterministic synthetic
    model; the monitor runs clock-free (wall time = sum of scripted
    latencies), so the whole run is a pure function of (seed, trace)."""
    from repro.serving.metrics import DecodeWindowMonitor
    from repro.serving.traffic import SyntheticServeModel, scripted_trace

    model = SyntheticServeModel(scripted_trace(args.traffic), seed=args.seed)
    total = windows if windows is not None else model.total_windows
    monitor = DecodeWindowMonitor()
    for w in range(total):
        plan = controller.next_window()
        phase = model.phase_at(w)
        monitor.begin_window()
        for lat in model.latencies(w, plan.config, plan.slice):
            monitor.record(lat, tokens=phase.batch)
        controller.observe(plan, monitor.end_window())


def _serve_windows_real(args, controller, windows):
    """Measured traffic: each window is one full serve (prefill + decode)
    under the planned config on real arrays. Compiled bundles would be
    rebuilt per config; mesh-topology knobs are pinned by the strategy's
    ``fixed=`` so every candidate runs on the host mesh we actually have."""
    from repro.configs.base import RunConfig
    from repro.core.space import SERVE_SPACE
    from repro.serving.metrics import DecodeWindowMonitor, WindowStats

    total = windows if windows is not None else 12
    inf = float("inf")
    for w in range(total):
        plan = controller.next_window()
        run = SERVE_SPACE.to_run_config(
            plan.config, RunConfig(mesh_model_parallel=args.model_parallel))
        # one metrics window per serve: all of this serve's decode steps
        monitor = DecodeWindowMonitor(
            clock=time.perf_counter, max_samples=4096)
        saved, args.window_steps = args.window_steps, max(args.max_new - 1, 1)
        try:
            _measured_serve(run, args, monitor)
            stats = monitor.history[-1]
        except Exception as exc:
            if plan.slice == "baseline":
                raise  # the incumbent must be runnable — nothing to fall back to
            # a candidate the executor cannot even run is an unserveable
            # window: infinite p99 trips the guard, which rolls back and
            # penalty-tells the strategy — crashing configs are contained
            # the same way regressing ones are
            print(f"window {w}: candidate failed ({type(exc).__name__}: "
                  f"{exc}); rolling back")
            stats = WindowStats(window=w, count=0, p50=inf, p99=inf,
                                mean=inf, max=inf, tokens_per_s=0.0,
                                wall_s=0.0)
        finally:
            args.window_steps = saved
        controller.observe(plan, stats)


def run_online(args) -> int:
    from repro.core.feasibility import make_prefilter
    from repro.core.space import SERVE_SPACE
    from repro.core.transfer import snap_into_space
    from repro.launch.tune import open_persistent_study
    from repro.serving.controller import GuardConfig, OnlineController
    from repro.serving.journal import OnlineJournal, surviving_baseline

    guard = GuardConfig(
        safety_p99=args.safety_p99,
        slice_frac=args.slice_frac,
        probation_windows=args.probation,
        promote_margin=args.promote_margin,
        warmup_windows=args.warmup_windows,
    )
    platform_key = online_platform_key(args)
    study = open_persistent_study(args.study, {})

    # resume semantics: the surviving baseline from this platform's previous
    # online sessions (last promote wins) outranks --tuned-config/defaults
    baseline = surviving_baseline(study, platform_key)
    resumed = baseline is not None
    if baseline is None:
        baseline = (load_tuned_config(args.tuned_config)
                    if args.tuned_config else snap_into_space(SERVE_SPACE, {}))

    # real traffic runs on the host mesh we actually have — pin the topology
    # knob (baseline and every proposal) so no config asks for a mesh this
    # machine can't build
    fixed = ({"mesh_model_parallel": args.model_parallel}
             if args.traffic == "real" else None)
    if fixed:
        baseline = {**baseline, **fixed}
    strategy = make_online_strategy(args, SERVE_SPACE, fixed=fixed)
    prefilter = make_prefilter(args.prefilter)

    with study:
        journal = OnlineJournal(
            study, platform_key,
            algorithm=f"online-{args.strategy}",
            guard=guard, baseline=baseline,
            strategy_args={
                "strategy": args.strategy, "seed": args.seed,
                "budget": args.budget, "traffic": args.traffic,
                "windows": args.windows, "resumed": resumed,
            },
        )
        controller = OnlineController(
            SERVE_SPACE, strategy, baseline,
            guard=guard, journal=journal, prefilter=prefilter,
            platform=platform_key,
        )
        if args.traffic == "real":
            _serve_windows_real(args, controller, args.windows)
        else:
            _serve_windows_synthetic(args, controller, args.windows)
        summary = controller.summary()
        journal.finish(summary)

    print(json.dumps(summary, indent=1, default=str))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
