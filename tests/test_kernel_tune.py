"""Kernel autotuning workload: the KernelEvaluator (numerics gate, fidelity,
spec round-trip), device-pinned subprocess workers, the tuned-table
round-trip into the public kernel entry points, snap idempotency, and the
honest-walltime / fidelity-detection regressions that rode along.

Worker-side functions must be module-level: the spawn start method ships
them to workers by pickle-by-reference.
"""
import json
import os
import pickle
import sys
import time

import pytest

from repro.core.evaluators import (
    FunctionEvaluator,
    WalltimeEvaluator,
    _accepts_fidelity,
)
from repro.core import executors
from repro.core.executors import (
    EvaluatorSpec,
    SubprocessBackend,
    _apply_pin_guard,
    _device_pin_env,
)
from repro.core.kernel_tune import (
    DEFAULT_SHAPES,
    KERNEL_NAMES,
    KERNEL_SPACES,
    KernelEvaluator,
    kernel_platform_key,
    kernel_similarity,
    make_kernel_evaluator,
    parse_kernel_platform,
    shape_class_for,
    tuned_entry,
    write_tuned_entries,
)
from repro.core.scheduler import TrialScheduler
from repro.core.study import EngineConfig
from repro.core.transfer import parse_namespace
from repro.kernels import (
    TUNED_TABLE_ENV,
    invalidate_tuned_table_cache,
    load_tuned_table,
    shape_class_distance,
    table_key,
    tuned_config,
)


# ------------------------------------------------------- evaluator protocol


def test_kernel_evaluator_ok_path_returns_finite_time():
    ev = make_kernel_evaluator("rwkv6", (1, 64, 2, 16), repeats=1,
                               interpret=True)
    t, info = ev(KERNEL_SPACES["rwkv6"].defaults())
    assert t < float("inf")
    assert info["kernel"] == "rwkv6"
    assert info["shape_class"] == "b1s64h2d16"
    assert info["max_rel_err"] < ev.tolerance
    assert "numerics_mismatch" not in info


def test_kernel_evaluator_numerics_gate_blocks_fast_wrong_variants():
    """A variant outside tolerance must return the infeasible penalty, not a
    timing — a fast-but-wrong block config can never become the incumbent."""
    ev = make_kernel_evaluator("rwkv6", (1, 64, 2, 16), repeats=1,
                               interpret=True, tolerance=0.0)  # nothing passes a zero gate
    t, info = ev(KERNEL_SPACES["rwkv6"].defaults())
    assert t == KernelEvaluator.INFEASIBLE
    assert info["numerics_mismatch"] is True
    assert "repeats" not in info  # gated BEFORE any timed run


def test_kernel_evaluator_fidelity_scales_repeats():
    ev = make_kernel_evaluator("rwkv6", (1, 64, 2, 16), repeats=4,
                               interpret=True)
    _, full = ev(KERNEL_SPACES["rwkv6"].defaults())
    _, half = ev(KERNEL_SPACES["rwkv6"].defaults(), fidelity=0.5)
    assert full["repeats"] == 4 and "fidelity" not in full
    assert half["repeats"] == 2 and half["fidelity"] == 0.5
    assert ev.supports_fidelity and not ev.parallel_safe


def test_kernel_evaluator_oversize_blocks_snap_not_crash():
    """Proposals beyond the (padded) sequence are legal: the ops-layer snap
    clamps them, so the search space never produces a hard failure."""
    ev = make_kernel_evaluator("flash_attention", (1, 200, 2, 2, 64),
                               repeats=1, interpret=True)
    t, info = ev({"block_q": 1024, "block_kv": 1024})
    assert t < float("inf") and "numerics_mismatch" not in info


def test_kernel_evaluator_spec_round_trips_through_pickle():
    """Subprocess workers rebuild the evaluator from its dotted-path spec;
    device arrays must never ride along in the pickle."""
    ev = make_kernel_evaluator("ssm_scan", (1, 64, 32, 8), repeats=2, seed=7,
                               interpret=True)
    ev._materialize()
    clone = pickle.loads(pickle.dumps(ev))
    assert clone._data is None  # arrays dropped at the process boundary
    assert clone.shape == ev.shape and clone.seed == 7

    rebuilt = ev.spec.resolve()
    assert isinstance(rebuilt, KernelEvaluator)
    assert (rebuilt.kernel, rebuilt.shape, rebuilt.repeats) == (
        "ssm_scan", (1, 64, 32, 8), 2)


def test_kernel_evaluator_rejects_bad_kernel_and_rank():
    with pytest.raises(ValueError, match="unknown kernel"):
        KernelEvaluator("conv2d", (1, 2, 3, 4))
    with pytest.raises(ValueError, match="dims"):
        KernelEvaluator("flash_attention", (1, 256, 4, 64))  # rank 4, not 5


# ----------------------------------------------- cells, namespace, transfer


def test_kernel_platform_key_round_trips_and_parses_as_cell():
    for kernel in KERNEL_NAMES:
        shape = DEFAULT_SHAPES[kernel][0]
        key = kernel_platform_key(kernel, "f32", shape_class_for(kernel, shape))
        assert parse_kernel_platform(key) == (
            kernel, "f32", shape_class_for(kernel, shape))
        cell = parse_namespace(key)
        assert cell.base == "kernel"
        assert cell.arch == f"{kernel}.f32"
    with pytest.raises(ValueError):
        parse_kernel_platform("wordcount")


def test_kernel_similarity_within_kernel_finite_across_infinite():
    flash_256 = parse_namespace(kernel_platform_key(
        "flash_attention", "f32", "b2s256h4k2d64"))
    flash_512 = parse_namespace(kernel_platform_key(
        "flash_attention", "f32", "b2s512h4k2d64"))
    rwkv = parse_namespace(kernel_platform_key("rwkv6", "f32", "b2s256h4d64"))
    flash_bf16 = parse_namespace(kernel_platform_key(
        "flash_attention", "bf16", "b2s256h4k2d64"))
    assert kernel_similarity(flash_256, flash_512) == 1.0  # one octave in s
    assert kernel_similarity(flash_256, flash_256) == 0.0
    assert kernel_similarity(flash_256, rwkv) == float("inf")
    assert kernel_similarity(flash_256, flash_bf16) == float("inf")


def test_shape_class_distance_dim_alphabets_must_match():
    assert shape_class_distance("b2s256h4d64", "b2s512h4d64") == 1.0
    assert shape_class_distance("b2s256h4d64", "b2s256di64n8") == float("inf")


# --------------------------------------------------- tuned table round-trip


def test_tuned_table_write_then_kernels_pick_it_up(tmp_path, monkeypatch):
    """A Study-tuned incumbent written to the table is consulted at call
    time by the public entry point when no explicit blocks are passed."""
    table = tmp_path / "tuned_table.json"
    write_tuned_entries(tuned_entry(
        "rwkv6", "f32", "b1s96h2d32", {"chunk": 16, "junk_knob": 9},
        time_s=0.01, source="test"), table)
    doc = json.loads(table.read_text())
    assert doc["version"] == 1
    rec = doc["entries"]["rwkv6|f32|b1s96h2d32"]
    assert rec["config"] == {"chunk": 16}  # knobs outside the space filtered

    monkeypatch.setenv(TUNED_TABLE_ENV, str(table))
    invalidate_tuned_table_cache()
    try:
        # exact hit, nearest same-kernel fallback, cross-kernel miss
        assert tuned_config("rwkv6", "f32", "b1s96h2d32") == {"chunk": 16}
        assert tuned_config("rwkv6", "f32", "b1s192h2d32") == {"chunk": 16}
        assert tuned_config("ssm_scan", "f32", "b1s96di2n32") is None

        import jax.numpy as jnp
        from unittest import mock

        from repro.kernels.rwkv6 import ops as rwkv_ops

        r = jnp.zeros((1, 96, 2, 32), jnp.float32)
        u = jnp.zeros((2, 32), jnp.float32)
        with mock.patch.object(rwkv_ops, "wkv6_chunked",
                               wraps=rwkv_ops.wkv6_chunked) as spy:
            rwkv_ops.wkv6(r, r, r, -jnp.ones_like(r), u, interpret=True)
            assert spy.call_args.kwargs["chunk"] == 16  # tuned value
            rwkv_ops.wkv6(r, r, r, -jnp.ones_like(r), u, chunk=64,
                          interpret=True)
            assert spy.call_args.kwargs["chunk"] == 64  # explicit arg wins
    finally:
        invalidate_tuned_table_cache()


def test_corrupt_tuned_table_warns_and_falls_back(tmp_path):
    bad = tmp_path / "tuned_table.json"
    bad.write_text("{not json")
    with pytest.warns(RuntimeWarning, match="tuned"):
        assert load_tuned_table(bad) == {}
    assert tuned_config("rwkv6", "f32", "b1s96h2d32", path=bad) is None


def test_missing_tuned_table_is_silently_empty(tmp_path):
    assert load_tuned_table(tmp_path / "nope.json") == {}


def test_write_tuned_entries_merges_and_invalidates(tmp_path):
    table = tmp_path / "t.json"
    write_tuned_entries(tuned_entry(
        "rwkv6", "f32", "b1s64h2d16", {"chunk": 32}, 0.1, "a"), table)
    assert tuned_config("rwkv6", "f32", "b1s64h2d16", path=table) == {
        "chunk": 32}
    # second write merges (old key survives) and the cache sees the update
    write_tuned_entries(tuned_entry(
        "rwkv6", "f32", "b1s64h2d16", {"chunk": 64}, 0.05, "b"), table)
    assert tuned_config("rwkv6", "f32", "b1s64h2d16", path=table) == {
        "chunk": 64}
    assert set(load_tuned_table(table)) == {table_key(
        "rwkv6", "f32", "b1s64h2d16")}


def test_shipped_tuned_table_is_valid_and_covers_all_kernels():
    """The checked-in artifact must load and carry an incumbent for every
    kernel (the acceptance round-trip the CI smoke exercises)."""
    invalidate_tuned_table_cache()
    entries = load_tuned_table()
    kernels = {key.split("|")[0] for key in entries}
    assert kernels == set(KERNEL_NAMES)
    for rec in entries.values():
        assert rec["config"] and rec["time_s"] > 0


# -------------------------------------------------------- snap idempotency


def test_snap_block_idempotent_and_clamps_to_padded_length():
    from repro.kernels.flash_attention.ops import snap_block

    # 128-snap first, then clamp to the 128-PADDED sequence — never below
    assert snap_block(100, 512) == 128      # floor at one MXU tile
    assert snap_block(512, 512) == 512
    assert snap_block(1024, 256) == 256     # clamped to padded s
    assert snap_block(256, 200) == 256      # padded(200)=256: NOT de-aligned
    assert snap_block(300, 512) == 256      # down-snap to a 128 multiple
    for block in (1, 100, 128, 200, 256, 1024):
        for s in (64, 200, 256, 512):
            once = snap_block(block, s)
            assert snap_block(once, s) == once
            assert once % 128 == 0


def test_snap_chunk_idempotent_both_kernels():
    from repro.kernels.rwkv6.ops import snap_chunk as rwkv_snap
    from repro.kernels.ssm_scan.ops import snap_chunk as ssm_snap

    for snap in (rwkv_snap, ssm_snap):
        assert snap(256, 160) == 160  # clamp to T
        assert snap(64, 160) == 64
        assert snap(0, 160) == 1
        for chunk in (1, 16, 64, 256):
            for s in (7, 96, 160, 512):
                once = snap(chunk, s)
                assert snap(once, s) == once and 1 <= once <= s


def test_snap_d_block_idempotent_and_divides():
    from repro.kernels.ssm_scan.ops import snap_d_block

    assert snap_d_block(1024, 64) == 64
    assert snap_d_block(128, 96) == 32  # halves until it divides
    for d_block in (16, 48, 256, 1024):
        for di in (32, 64, 96):
            once = snap_d_block(d_block, di)
            assert snap_d_block(once, di) == once
            assert di % once == 0


# ------------------------------------------------ satellite: device pinning


def test_pin_env_narrows_existing_cuda_list(monkeypatch):
    monkeypatch.setenv("CUDA_VISIBLE_DEVICES", "3, 5,7")
    assert _device_pin_env(1, 3) == {"CUDA_VISIBLE_DEVICES": "5"}
    assert _device_pin_env(4, 3) == {"CUDA_VISIBLE_DEVICES": "5"}  # wraps


def test_pin_env_gpu_platform_uses_slot_index(monkeypatch):
    monkeypatch.delenv("CUDA_VISIBLE_DEVICES", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cuda")
    assert _device_pin_env(2, 4) == {"CUDA_VISIBLE_DEVICES": "2"}


def test_pin_env_tpu_bounds_one_chip_per_process(monkeypatch):
    monkeypatch.delenv("CUDA_VISIBLE_DEVICES", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    env = _device_pin_env(3, 4)
    assert env["TPU_VISIBLE_CHIPS"] == "3"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"


def test_pin_env_cpu_fallback_strips_inherited_device_count(monkeypatch):
    monkeypatch.delenv("CUDA_VISIBLE_DEVICES", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv(
        "XLA_FLAGS",
        "--xla_foo=1 --xla_force_host_platform_device_count=512")
    env = _device_pin_env(0, 2)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "device_count=512" not in env["XLA_FLAGS"]
    assert "--xla_force_host_platform_device_count=1" in env["XLA_FLAGS"]
    assert "--xla_foo=1" in env["XLA_FLAGS"]  # unrelated flags survive


@pytest.mark.parametrize("worker_id", [None, "0"])
def test_pin_env_tpu_host_never_yields_cpu(monkeypatch, worker_id):
    """With no platform asked for, a host with TPU chips pins each worker to
    one chip — never to the host CPU."""
    monkeypatch.delenv("CUDA_VISIBLE_DEVICES", raising=False)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    if worker_id is None:
        monkeypatch.delenv("TPU_WORKER_ID", raising=False)
        monkeypatch.setattr(executors, "_tpu_chips_on_host", lambda: 4)
    else:
        monkeypatch.setenv("TPU_WORKER_ID", worker_id)
    envs = [_device_pin_env(slot, 4) for slot in range(4)]
    for slot, env in enumerate(envs):
        assert "JAX_PLATFORMS" not in env
        assert env["TPU_VISIBLE_CHIPS"] == str(slot)
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert len({env["TPU_PROCESS_PORT"] for env in envs}) == 4


def test_pin_env_cpu_only_host_pins_cpu(monkeypatch):
    monkeypatch.delenv("CUDA_VISIBLE_DEVICES", raising=False)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("TPU_WORKER_ID", raising=False)
    monkeypatch.setattr(executors, "_tpu_chips_on_host", lambda: 0)
    assert _device_pin_env(1, 2)["JAX_PLATFORMS"] == "cpu"


def test_pin_guard_passes_without_jax_or_pin():
    assert _apply_pin_guard(None) is None
    assert _apply_pin_guard({}) is None


class _FakeDevice:
    def __init__(self, platform):
        self.platform = platform


class _FakeJax:
    def __init__(self, *platforms):
        self._devices = [_FakeDevice(p) for p in platforms]

    def devices(self):
        return self._devices


def test_pin_guard_checks_count_and_platform(monkeypatch):
    tpu_pin = {"TPU_VISIBLE_CHIPS": "0"}
    monkeypatch.setitem(sys.modules, "jax", _FakeJax("tpu"))
    assert _apply_pin_guard(tpu_pin) is None
    monkeypatch.setitem(sys.modules, "jax", _FakeJax("cpu"))
    assert "pinned to 'tpu'" in _apply_pin_guard(tpu_pin)
    monkeypatch.setitem(sys.modules, "jax", _FakeJax("tpu", "tpu"))
    assert "sees 2 devices" in _apply_pin_guard(tpu_pin)
    monkeypatch.setitem(sys.modules, "jax", _FakeJax("cpu"))
    assert _apply_pin_guard({"JAX_PLATFORMS": "cpu"}) is None


def _pin_probe(cfg):
    """Worker-side: 1.0 iff the CPU pin env took before this process ran."""
    ok = (os.environ.get("JAX_PLATFORMS") == "cpu"
          and "--xla_force_host_platform_device_count=1"
          in os.environ.get("XLA_FLAGS", ""))
    return 1.0 if ok else 0.0


def make_pin_probe_evaluator():
    return FunctionEvaluator(_pin_probe)


def test_pinned_workers_see_pin_env_and_distinct_slots():
    backend = SubprocessBackend(
        spec=EvaluatorSpec.factory("test_kernel_tune:make_pin_probe_evaluator"),
        pin_devices=2,
    )
    with TrialScheduler(FunctionEvaluator(_pin_probe), backend=backend,
                        max_workers=2) as sched:
        trials = sched.evaluate_batch([{"x": i} for i in range(4)])
        slots = {w.pin_slot for w in backend._workers}
    assert [t.time_s for t in trials] == [1.0] * 4  # env inside every worker
    assert slots == {0, 1}  # round-robin over distinct device slots


def test_unpinned_workers_do_not_get_pin_env():
    backend = SubprocessBackend(
        spec=EvaluatorSpec.factory("test_kernel_tune:make_pin_probe_evaluator"),
    )
    with TrialScheduler(FunctionEvaluator(_pin_probe), backend=backend,
                        max_workers=1) as sched:
        trial = sched.evaluate_batch([{"x": 0}])[0]
    assert trial.time_s == 0.0  # no pin requested -> env untouched


def test_pin_devices_validation():
    with pytest.raises(ValueError, match="positive"):
        SubprocessBackend(pin_devices=0)
    with pytest.raises(ValueError, match="subprocess"):
        TrialScheduler(FunctionEvaluator(_pin_probe), pin_devices=2)
    with pytest.raises(ValueError, match="subprocess"):
        EngineConfig(pin_devices=2)
    with pytest.raises(ValueError, match="pin_devices"):
        EngineConfig(isolation="subprocess", pin_devices=0)
    cfg = EngineConfig(isolation="subprocess", pin_devices=2)
    assert cfg.scheduler_kwargs()["pin_devices"] == 2


# ---------------------------- satellite: honest async walltime measurement


class _LazyResult:
    """Mimics a jax array mid-flight: the work only 'finishes' when someone
    blocks on it."""

    def __init__(self, delay_s):
        self.delay_s = delay_s

    def block_until_ready(self):
        time.sleep(self.delay_s)
        return self


def test_walltime_evaluator_blocks_on_async_results():
    """Async dispatch returns immediately; an evaluator that doesn't block
    times the enqueue (~0s) instead of the work. The measured time must
    include the materialization delay."""
    delay = 0.05
    ev = WalltimeEvaluator(lambda cfg: (lambda: _LazyResult(delay)), repeats=1)
    t, _ = ev({})
    assert t >= delay * 0.9, t


def test_walltime_evaluator_tolerates_none_and_scalar_returns():
    t_none, _ = WalltimeEvaluator(lambda cfg: (lambda: None), repeats=1)({})
    t_scalar, _ = WalltimeEvaluator(lambda cfg: (lambda: 42.0), repeats=1)({})
    assert t_none < 1.0 and t_scalar < 1.0


# ------------------------------- satellite: fidelity detection regression


def test_accepts_fidelity_rejects_bare_var_keyword():
    """**kwargs would silently swallow fidelity=, run the full job, and get
    ranked by ASHA under a low-fidelity key — it must NOT qualify."""

    def swallows_everything(cfg, **kwargs):
        return 1.0

    def explicit(cfg, fidelity=1.0):
        return 1.0

    def keyword_only(cfg, *, fidelity):
        return 1.0

    def plain(cfg):
        return 1.0

    assert not _accepts_fidelity(swallows_everything)
    assert _accepts_fidelity(explicit)
    assert _accepts_fidelity(keyword_only)
    assert not _accepts_fidelity(plain)
    assert not _accepts_fidelity(len)  # C callable: no signature, no crash


def test_accepts_fidelity_opt_in_attribute_for_forwarding_wrappers():
    def wrapper(cfg, **kwargs):
        return 1.0

    wrapper.accepts_fidelity = True
    assert _accepts_fidelity(wrapper)
    assert FunctionEvaluator(wrapper).supports_fidelity


def test_function_evaluator_never_leaks_fidelity_into_plain_fn():
    seen = []

    def plain(cfg):
        seen.append(cfg)
        return 1.0

    ev = FunctionEvaluator(plain)
    assert not ev.supports_fidelity
    ev({"x": 1}, fidelity=0.25)  # swallowed by the evaluator, not the fn
    assert seen == [{"x": 1}]
