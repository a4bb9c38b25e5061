#!/usr/bin/env python3
"""Bring the system up on a TPU, through its own entry points, and check what
comes out.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # four chips: only what exists across chips

One chip, one process:
  serve    llama3.2-1b at full width through repro.launch.serve's measured
           path (batch 4, prompt 128, 32 new tokens). The timed prefill and
           first decode step are replayed on this process's CPU backend.
  train    whisper-tiny at full width (8 x 448) as repro.launch.train wires
           it; at least 5 steps, every loss finite.
  kernels  flash_attention, wkv6 and selective_scan compiled for the chip at
           real widths, each checked against its ref.py run on the CPU.
  tuner    one KernelEvaluator trial compiled for the chip (interpret=False),
           the tuner's on-device measurement path.

Four chips (--chips 4), and no other phase:
  fanout   four pinned tuner workers (SubprocessBackend(pin_devices=4)), run
           before this process touches a chip: each sees exactly one TPU.
  serve    gemma2-9b at full depth, model-parallel over the four chips.
  shard    gemma2-9b cut to two layers at its published widths, on one chip
           and sharded over four: prefill and decode logits must agree.

Weights and inputs are random, made from fixed seeds. Compilation goes to
JAX's persistent cache (repro.launch.compile_cache), so a second run shows
lower compile seconds. Exits non-zero without a TPU and when any phase
fails; a passing run's last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import tempfile
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

# ------------------------------------------------------------- tolerances
#
# Logits are compared as log-probabilities. Metric: the largest
# |log_softmax(a) - log_softmax(b)| over every (request, vocab) entry, and
# top-1 agreement — for every request, the token one side ranks first has a
# log-prob on the other side within the tolerance of that side's best (random
# weights leave near-ties that any rounding flips). How far bf16 rounding
# alone moves log-probs depends on the random weights, so the tolerance is
# measured on the reference side: the same metric between the bf16 program
# and the same weights run in f32, times BF16_NOISE_FACTOR, plus
# LOGIT_TOL_FLOOR. A broken layout or kernel moves log-probs by whole units.
BF16_NOISE_FACTOR = 2.0
LOGIT_TOL_FLOOR = 0.05
# Kernels: max |out - ref| / max |ref| against ref.py, at the tuner's own
# numerics-gate tolerance for the dtype (repro.core.kernel_tune).

SEED = 0

# real widths: flash at llama3.2-1b prefill (q, k/v), wkv6 at rwkv6-7b, the
# selective scan at jamba-1.5-large (x, state size N)
FLASH_SHAPES = ((1, 2048, 32, 64), (1, 2048, 8, 64))
WKV6_SHAPE = (1, 512, 64, 64)
SSM_SHAPE = ((1, 512, 16384), 16)
TUNER_SHAPE = (1, 2048, 32, 8, 64)  # flash B x S x Hq x Hkv x Dh


class PhaseFailed(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise PhaseFailed(msg)


# --------------------------------------------------------------- plumbing


class CompileClock:
    """Seconds jax spent in backend compilation (a persistent-cache load
    counts as its retrieval time), programs compiled, and persistent-cache
    hits, since the last ``take()``."""

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


def peak_bytes(devices):
    stats = [d.memory_stats() or {} for d in devices]
    return max(s.get("peak_bytes_in_use", 0) for s in stats)


def log_softmax32(x):
    import jax
    import jax.numpy as jnp

    return jax.nn.log_softmax(jnp.asarray(x, jnp.float32), axis=-1)


def logit_gap(a, b) -> float:
    import numpy as np

    la, lb = (np.asarray(log_softmax32(x)) for x in (a, b))
    return float(np.max(np.abs(la - lb))) if la.size else 0.0


def top1_agrees(a, b, tol) -> bool:
    import numpy as np

    la, lb = (np.asarray(log_softmax32(x)) for x in (a, b))
    rows = np.arange(la.shape[0])
    a_on_b = lb[rows, la.argmax(-1)] >= lb.max(-1) - tol
    b_on_a = la[rows, lb.argmax(-1)] >= la.max(-1) - tol
    return bool(np.all(a_on_b) and np.all(b_on_a))


def compare_logits(tag, got, want, want_f32):
    """``got`` against the bf16 reference ``want``, with the tolerance
    measured from ``want`` against the same weights in f32."""
    noise = logit_gap(want, want_f32)
    tol = BF16_NOISE_FACTOR * noise + LOGIT_TOL_FLOOR
    gap = logit_gap(got, want)
    agree = top1_agrees(got, want, tol)
    print(f"  {tag}: max |dlogp| {gap:.4f} (tol {tol:.4f} = "
          f"{BF16_NOISE_FACTOR} x bf16-vs-f32 {noise:.4f} + {LOGIT_TOL_FLOOR}), "
          f"top-1 agree {agree}")
    check(gap <= tol, f"{tag}: log-prob gap {gap:.4f} > tol {tol:.4f}")
    check(agree, f"{tag}: top-1 disagreement beyond tol {tol:.4f}")


def serve_args(argv):
    from repro.launch.serve import build_parser

    return build_parser().parse_args(argv)


def served_checks(tag, served, args, vocab):
    import numpy as np

    tokens = np.asarray(served.tokens)
    check(tokens.shape == (args.batch, args.max_new),
          f"{tag}: generated {tokens.shape}, want {(args.batch, args.max_new)}")
    check(bool(np.all((tokens >= 0) & (tokens < vocab))),
          f"{tag}: token ids outside the vocabulary")
    for name in ("prefill_logits", "decode_logits"):
        x = np.asarray(getattr(served, name), np.float32)
        check(bool(np.all(np.isfinite(x))), f"{tag}: non-finite {name}")


def report_serve(tag, served, args, monitor):
    steps = args.max_new - 1
    agg = monitor.aggregate()
    print(f"  {tag}: prefill {args.batch}x{args.prompt_len} "
          f"{served.t_prefill * 1e3:.3f} ms")
    print(f"  {tag}: decode {steps} steps x {args.batch} requests in "
          f"{served.t_decode:.4f} s, p50 {agg.p50 * 1e3:.3f} ms/step, "
          f"p99 {agg.p99 * 1e3:.3f} ms/step, "
          f"{args.batch * steps / served.t_decode:.1f} tok/s")


def cpu_replay(arch, run, args, served, cpu):
    """The timed prefill and first decode step, replayed on the CPU backend
    with the same weights and tokens: once as the same bf16 program, once in
    f32. Returns ((prefill_bf16, decode_bf16), (prefill_f32, decode_f32))."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import ShapeConfig
    from repro.distributed.steps import make_decode_step, make_prefill_step
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import grow_caches

    mesh = make_host_mesh(1, devices=[cpu])
    pre_shape = ShapeConfig("ref_prefill", args.prompt_len, args.batch, "prefill")
    dec_shape = ShapeConfig("ref_decode", args.prompt_len + args.max_new,
                            args.batch, "decode")
    batch = jax.device_put(served.batch, cpu)
    step = {"tokens": jax.device_put(served.tokens[:, :1], cpu),
            "cache_len": jax.device_put(jnp.asarray(args.prompt_len, jnp.int32), cpu)}
    out = []
    for r, dtype in ((run, None),
                     (run.replace(matmul_precision="f32", weight_dtype="float32"),
                      jnp.float32)):
        params = jax.device_put(served.params, cpu)
        if dtype is not None:
            params = jax.tree.map(lambda x: x.astype(dtype), params)
        with jax.set_mesh(mesh):
            pre = make_prefill_step(arch, r, pre_shape, mesh)
            dec = make_decode_step(arch, r, dec_shape, mesh)
            logits, caches = pre.jit()(params, batch)
            dlogits, _ = dec.jit()(params, grow_caches(caches, args.max_new), step)
        out.append((jax.device_get(logits), jax.device_get(dlogits)))
    return out


# ------------------------------------------------------ one-chip phases


def phase_serve():
    import jax

    from repro.configs.archs import get_arch
    from repro.configs.base import RunConfig
    from repro.launch.serve import _measured_serve
    from repro.serving.metrics import DecodeWindowMonitor

    args = serve_args(["--arch", "llama3.2-1b", "--batch", "4",
                       "--prompt-len", "128", "--max-new", "32"])
    arch = get_arch(args.arch)
    run = RunConfig(mesh_model_parallel=args.model_parallel)
    monitor = DecodeWindowMonitor(clock=time.perf_counter)
    served = _measured_serve(run, args, monitor)
    report_serve("llama3.2-1b", served, args, monitor)
    served_checks("llama3.2-1b", served, args, arch.vocab_size)

    t0 = time.perf_counter()
    (ref_pre, ref_dec), (f32_pre, f32_dec) = cpu_replay(
        arch, run, args, served, jax.devices("cpu")[0])
    print(f"  CPU replay (bf16 + f32) {time.perf_counter() - t0:.1f} s")
    compare_logits("prefill logits, chip vs CPU", served.prefill_logits,
                   ref_pre, f32_pre)
    compare_logits("decode-step logits, chip vs CPU", served.decode_logits,
                   ref_dec, f32_dec)


def phase_train():
    import jax

    from repro.checkpoint.manager import CheckpointManager
    from repro.configs.archs import get_arch
    from repro.configs.base import RunConfig, ShapeConfig
    from repro.data.pipeline import PipelineConfig, SyntheticLMPipeline
    from repro.distributed.steps import init_train_state, make_train_step
    from repro.ft.runner import ResilientTrainer, RunnerConfig
    from repro.launch.mesh import make_host_mesh

    steps = 6
    arch = get_arch("whisper-tiny")
    shape = ShapeConfig("smoke_train", 448, 8, "train")
    run = RunConfig(mesh_model_parallel=1)
    mesh = make_host_mesh(1)
    with tempfile.TemporaryDirectory() as ckpt_dir, jax.set_mesh(mesh):
        bundle = make_train_step(arch, run, shape, mesh)
        state = init_train_state(bundle)
        pipeline = SyntheticLMPipeline(
            arch, shape, PipelineConfig(), mesh=mesh,
            batch_sharding=bundle.in_shardings[1],
        )
        trainer = ResilientTrainer(
            step_fn=bundle.jit(), state=state, pipeline=pipeline,
            ckpt=CheckpointManager(ckpt_dir, keep_n=1),
            cfg=RunnerConfig(total_steps=steps, checkpoint_every=steps),
        )
        t0 = time.perf_counter()
        trainer.run()
        wall = time.perf_counter() - t0
    losses = [h["loss"] for h in trainer.history]
    print(f"  whisper-tiny {shape.global_batch}x{shape.seq_len}: "
          f"{len(losses)} steps in {wall:.2f} s (first step compiles), "
          f"losses {[round(x, 4) for x in losses]}")
    check(len(losses) >= 5, f"only {len(losses)} train steps ran")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")


def _rel_err(out, ref):
    import numpy as np

    out, ref = (np.asarray(x, np.float32) for x in (out, ref))
    return float(np.max(np.abs(out - ref)) / (np.max(np.abs(ref)) + 1e-9))


def phase_kernels():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.kernel_tune import _DEFAULT_TOL
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.rwkv6.ops import wkv6
    from repro.kernels.rwkv6.ref import wkv6_ref
    from repro.kernels.ssm_scan.ops import selective_scan
    from repro.kernels.ssm_scan.ref import ssm_scan_ref

    tpu, cpu = jax.devices()[0], jax.devices("cpu")[0]
    rng = np.random.default_rng(SEED)
    normal = lambda *shape: rng.standard_normal(shape).astype(np.float32)

    q_shape, kv_shape = FLASH_SHAPES
    q = normal(*q_shape) * q_shape[-1] ** -0.5  # pre-scaled, as the model does
    flash_in = [x.astype(jnp.bfloat16)
                for x in (q, normal(*kv_shape), normal(*kv_shape))]
    r, kk, vv = (0.5 * normal(*WKV6_SHAPE) for _ in range(3))
    logw = -np.exp(0.3 * normal(*WKV6_SHAPE))
    u = 0.3 * normal(*WKV6_SHAPE[2:])
    (b, s, di), n = SSM_SHAPE
    dt = np.log1p(np.exp(normal(b, s, di)))
    xs = normal(b, s, di)
    bt, ct = normal(b, s, n), normal(b, s, n)
    a = -np.exp(0.3 * normal(di, n))

    cases = [
        ("flash_attention", "bf16",
         lambda *x: flash_attention(*x, causal=True, scale=1.0),
         lambda *x: attention_ref(*x, causal=True, scale=1.0), flash_in),
        ("wkv6", "f32", wkv6, wkv6_ref, [r, kk, vv, logw, u]),
        ("selective_scan", "f32", selective_scan, ssm_scan_ref,
         [dt, xs, bt, ct, a]),
    ]
    for name, dtype, kernel, ref, inputs in cases:
        on_chip = [jax.device_put(x, tpu) for x in inputs]
        fn = jax.jit(kernel)
        text = fn.lower(*on_chip).compile().as_text()
        check("tpu_custom_call" in text, f"{name}: no Mosaic kernel in the program")
        out = jax.block_until_ready(fn(*on_chip))
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*on_chip))
        t_run = time.perf_counter() - t0
        want = jax.jit(ref)(*[jax.device_put(x, cpu) for x in inputs])
        err, tol = _rel_err(out, want), _DEFAULT_TOL[dtype]
        print(f"  {name} {dtype} {tuple(inputs[0].shape)}: compiled (tpu_custom_call), "
              f"one run {t_run * 1e3:.3f} ms, max rel err vs ref.py {err:.2e} "
              f"(tol {tol:g})")
        check(err <= tol, f"{name}: rel err {err:.2e} > {tol:g}")


def phase_tuner():
    from repro.core.kernel_tune import KERNEL_SPACES, make_kernel_evaluator

    ev = make_kernel_evaluator("flash_attention", TUNER_SHAPE, "bf16",
                               repeats=3, interpret=False, seed=SEED)
    config = KERNEL_SPACES["flash_attention"].defaults()
    t, info = ev(config)
    print(f"  KernelEvaluator(flash_attention bf16 {ev.shape_class()}, "
          f"interpret=False) {config}: best of {info.get('repeats')} "
          f"{t * 1e3:.3f} ms, max rel err {info['max_rel_err']:.2e}")
    check(math.isfinite(t) and not info.get("numerics_mismatch"),
          f"tuner trial failed: {info}")


# ----------------------------------------------------- four-chip phases


class DeviceProbe:
    """Tuner evaluator run inside each pinned worker: reports the devices
    that worker's jax sees after running one op on them."""

    parallel_safe = False
    supports_fidelity = False

    def __call__(self, config):
        import jax
        import jax.numpy as jnp

        # hold every worker inside a trial until all of them are, so each
        # trial lands on a different worker (and so a different chip)
        barrier = Path(config["barrier"])
        (barrier / str(os.getpid())).touch()
        deadline = time.monotonic() + 300
        while len(list(barrier.iterdir())) < config["workers"]:
            if time.monotonic() > deadline:
                raise TimeoutError("pinned workers never all arrived")
            time.sleep(0.05)
        devices = jax.devices()
        total = float(jnp.sum(jnp.arange(1024, dtype=jnp.float32)))
        return 0.0, {
            "pid": os.getpid(),
            "count": len(devices),
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "chip": os.environ.get("TPU_VISIBLE_CHIPS", ""),
            "sum": total,
        }


def make_device_probe():
    """Worker-side factory: starting jax here makes the pin guard check the
    worker's devices before it reports ready."""
    import jax

    jax.devices()
    return DeviceProbe()


def phase_fanout(n):
    from repro.core.executors import EvaluatorSpec, SubprocessBackend
    from repro.core.scheduler import TrialScheduler

    backend = SubprocessBackend(
        spec=EvaluatorSpec.factory("chip_smoke:make_device_probe"),
        pin_devices=n, worker_init_timeout_s=300.0,
    )
    with tempfile.TemporaryDirectory() as barrier, TrialScheduler(
        DeviceProbe(), backend=backend, max_workers=n, timeout_s=600.0,
    ) as sched:
        trials = sched.evaluate_batch(
            [{"barrier": barrier, "workers": n, "i": i} for i in range(n)])
    for t in trials:
        check(t.ok, f"pinned worker trial failed: {t.error}")
        i = t.info
        print(f"  worker pid {i['pid']} (TPU_VISIBLE_CHIPS={i['chip']}): "
              f"{i['count']} device, {i['platform']} {i['kind']}")
    check(len({t.info["pid"] for t in trials}) == n,
          f"{n} trials did not land on {n} workers")
    check(len({t.info["chip"] for t in trials}) == n, "workers share a chip")
    check(all(t.info["count"] == 1 and t.info["platform"] == "tpu"
              and t.info["sum"] == 523776.0 for t in trials),
          "a pinned worker does not see exactly one TPU device")


def phase_serve_9b():
    from repro.configs.archs import get_arch
    from repro.configs.base import RunConfig
    from repro.launch.serve import _measured_serve
    from repro.serving.metrics import DecodeWindowMonitor

    args = serve_args(["--arch", "gemma2-9b", "--batch", "4",
                       "--prompt-len", "128", "--max-new", "16",
                       "--model-parallel", "4"])
    run = RunConfig(mesh_model_parallel=args.model_parallel)
    monitor = DecodeWindowMonitor(clock=time.perf_counter)
    served = _measured_serve(run, args, monitor)
    report_serve("gemma2-9b mp=4", served, args, monitor)
    served_checks("gemma2-9b mp=4", served, args, get_arch(args.arch).vocab_size)


def phase_shard():
    import jax
    import jax.numpy as jnp

    from repro.configs.archs import get_arch
    from repro.configs.base import RunConfig, ShapeConfig
    from repro.distributed.steps import make_decode_step, make_prefill_step
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import grow_caches

    layers, batch, prompt, extra = 2, 4, 128, 8
    arch = dataclasses.replace(get_arch("gemma2-9b"), num_layers=layers)
    pre_shape = ShapeConfig("shard_prefill", prompt, batch, "prefill")
    dec_shape = ShapeConfig("shard_decode", prompt + extra, batch, "decode")

    def run_steps(run, mesh, params, tokens=None):
        with jax.set_mesh(mesh):
            pre = make_prefill_step(arch, run, pre_shape, mesh)
            dec = make_decode_step(arch, run, dec_shape, mesh)
            if params is None:
                params = pre.init_params(jax.random.PRNGKey(SEED))
            (params,) = pre.place(mesh, params)
            inputs = pre.model.make_inputs(pre_shape)
            logits, caches = pre.jit()(params, inputs)
            if tokens is None:
                tokens = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            step = {"tokens": tokens,
                    "cache_len": jnp.asarray(prompt, jnp.int32)}
            dlogits, _ = dec.jit()(params, grow_caches(caches, extra), step)
            return params, tokens, jax.device_get((logits, dlogits))

    one = make_host_mesh(1, devices=jax.devices()[:1])
    four = make_host_mesh(4)
    run1, run4 = RunConfig(mesh_model_parallel=1), RunConfig(mesh_model_parallel=4)
    params, tokens, (pre1, dec1) = run_steps(run1, one, None)
    _, _, (pre4, dec4) = run_steps(run4, four, params, tokens)
    run32 = run1.replace(matmul_precision="f32", weight_dtype="float32")
    params32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        _, _, (pre32, dec32) = run_steps(run32, one, params32, tokens)
    print(f"  gemma2-9b cut to {layers} layers, batch {batch} x prompt {prompt}")
    compare_logits("prefill logits, 4 chips vs 1", pre4, pre1, pre32)
    compare_logits("decode-step logits, 4 chips vs 1", dec4, dec1, dec32)


ONE_CHIP = [("serve", phase_serve), ("train", phase_train),
            ("kernels", phase_kernels), ("tuner", phase_tuner)]
FOUR_CHIPS = [("serve", phase_serve_9b), ("shard", phase_shard)]


# ------------------------------------------------------------------ main


def device_problem(devices, chips):
    """Why this run cannot go on, or None: a chip is required, never a
    fallback."""
    if devices[0].platform != "tpu":
        return f"no TPU: jax found {devices[0].platform} devices"
    if len(devices) != chips:
        return f"{chips} chip(s) asked for, jax found {len(devices)}"
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip phases; 4: only the phases that "
                         "exist across four chips")
    args = ap.parse_args(argv)
    failures = []

    if args.chips == 4:
        # before this process starts a backend, which would take every chip
        print("[fanout] pinned tuner workers")
        try:
            phase_fanout(4)
        except Exception:  # noqa: BLE001 — reported, and fails the run
            traceback.print_exc()
            failures.append("fanout")

    import jax

    # the CPU backend carries the references the chip is compared with
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        jax.config.update("jax_platforms", platforms + ",cpu")
    cache_dir = use_compile_cache()
    devices = jax.devices()
    problem = device_problem(devices, args.chips)
    if problem:
        print(problem, file=sys.stderr)
        return 1

    import importlib.metadata

    import jaxlib

    print(f"device: {devices[0].device_kind} x {len(devices)} "
          f"(platform {devices[0].platform})")
    print(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
          f"libtpu {importlib.metadata.version('libtpu')}")
    print(f"compile cache: {cache_dir}")

    clock = CompileClock()
    for name, phase in (ONE_CHIP if args.chips == 1 else FOUR_CHIPS):
        print(f"[{name}]")
        clock.take()
        t0 = time.perf_counter()
        try:
            phase()
            status = "ok"
        except Exception:  # noqa: BLE001 — reported, and fails the run
            traceback.print_exc()
            failures.append(name)
            status = "FAILED"
        secs, programs, hits = clock.take()
        print(f"[{name}] {status} in {time.perf_counter() - t0:.1f} s; "
              f"compile {secs:.2f} s over {programs} programs "
              f"({hits} persistent-cache hits); peak device memory "
              f"{peak_bytes(devices) / 2**30:.2f} GiB")
        gc.collect()

    if failures:
        print(f"FAILED phases: {', '.join(failures)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
