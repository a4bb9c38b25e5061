"""The program's named scopes and host spans, and the benchmark's reading of
them (``chipbench/scopes.py``), on the CPU.

The step programs carry every documented scope as a component of their
operations' scope paths, keep their module names, and compute bit for bit
what they compute without the scopes. The input pipeline's spans reach a
profiler trace's host plane. ``chipbench/scopes.py`` reads traces recorded
on a TPU v5e: ``trace_small`` with the numbers ``trace.py`` gives,
``trace_scoped`` with its scopes and program spans.
"""
from __future__ import annotations

import contextlib
import glob
import gzip
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import run, scopes, trace  # noqa: E402
from repro.configs.archs import get_arch  # noqa: E402
from repro.configs.base import RunConfig, ShapeConfig  # noqa: E402
from repro.data.pipeline import PipelineConfig, SyntheticLMPipeline  # noqa: E402
from repro.distributed.steps import (  # noqa: E402
    init_train_state,
    make_decode_step,
    make_prefill_step,
    make_train_step,
)
from repro.launch.mesh import make_host_mesh  # noqa: E402

DATA = ROOT / "chipbench" / "tests" / "data"
MAKERS = {"train": make_train_step, "prefill": make_prefill_step, "decode": make_decode_step}
LAYER = {"attn", "qkv", "sdpa", "out"}
SERVE = {"embed", "kv_cache", "mlp", "logits"} | LAYER
TRAIN = {"embed", "mlp", "logits", "loss", "optimizer"} | LAYER
# (architecture, step) -> the scopes its program must carry
CASES = {
    # a bfloat16 cache is the projections' own output: no kv_cache operation
    ("internvl2-26b", "prefill"): SERVE - {"kv_cache"},
    ("internvl2-26b", "decode"): SERVE,
    ("internvl2-26b", "train"): TRAIN,
    ("whisper-tiny", "train"): TRAIN | {"encoder", "xattn"},
    ("whisper-tiny", "decode"): SERVE | {"xattn"},
    ("jamba-1.5-large-398b", "train"): TRAIN | {"moe", "mamba"},
    ("rwkv6-7b", "train"): {"embed", "rwkv", "logits", "loss", "optimizer"},
}
_TEXT: dict = {}


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """Compile without the persistent cache: its key leaves op metadata out,
    so a program with the scopes and the same one without them share an
    entry (an entry point an earlier test ran may have turned the cache on
    in this process)."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def bundle(name, kind, seq=32, batch=2):
    mesh = make_host_mesh(1, devices=jax.devices()[:1])
    with jax.set_mesh(mesh):
        return MAKERS[kind](get_arch(name, smoke=True), RunConfig(),
                            ShapeConfig("s", seq, batch, kind), mesh), mesh


def compiled_text(name, kind):
    """The compiled CPU HLO of the architecture's smoke-size step."""
    if (name, kind) not in _TEXT:
        b, mesh = bundle(name, kind)
        with jax.set_mesh(mesh):
            _TEXT[name, kind] = b.lower().compile().as_text()
    return _TEXT[name, kind]


def scope_components_in(hlo: str) -> set:
    return {c for path in re.findall(r'op_name="([^"]*)"', hlo)
            for c in scopes.scope_components(path)}


@pytest.mark.parametrize("name,kind", sorted(CASES))
def test_step_programs_carry_the_model_scopes(name, kind):
    assert CASES[name, kind] <= scope_components_in(compiled_text(name, kind))


@pytest.mark.parametrize("kind", ["prefill", "decode", "train"])
def test_step_program_names_are_stable(kind):
    assert compiled_text("internvl2-26b", kind).startswith(f"HloModule jit_{kind}_step,")


def _without_metadata(hlo: str) -> str:
    """The program's computations without source metadata (scope paths,
    source lines), the tables of source locations and the numbers that
    make instruction names unique."""
    tables = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
    blocks = [b for b in hlo.split("\n\n") if not b.startswith(tables)]
    text = re.sub(r", metadata=\{[^}]*\}", "", "\n\n".join(blocks))
    return re.sub(r"([%\w-])\.\d+\b", r"\1", text)


def _scoped_and_unscoped(fn, args, monkeypatch):
    """``fn`` compiled as it is and with every ``jax.named_scope`` a no-op:
    (compiled, HLO text) of each."""
    def build():
        compiled = jax.jit(lambda *a: fn(*a)).lower(*args).compile()
        return compiled, compiled.as_text()

    scoped = build()
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        unscoped = build()
    assert "sdpa" in scoped[1] and "sdpa" not in unscoped[1]
    assert _without_metadata(scoped[1]) == _without_metadata(unscoped[1])
    return scoped[0], unscoped[0]


def test_named_scopes_leave_logits_bit_identical(monkeypatch):
    b, mesh = bundle("internvl2-26b", "prefill", seq=16)
    d, _ = bundle("internvl2-26b", "decode", seq=16)
    with jax.set_mesh(mesh):
        params = b.init_params(jax.random.PRNGKey(0))
        batch = b.model.make_inputs(ShapeConfig("s", 16, 2, "prefill"), jax.random.PRNGKey(1))
        pre = _scoped_and_unscoped(b.fn, (params, batch), monkeypatch)
        (l1, c1), (l2, c2) = (f(params, batch) for f in pre)
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
        step = {"tokens": jnp.ones((2, 1), jnp.int32), "cache_len": jnp.asarray(15, jnp.int32)}
        dec = _scoped_and_unscoped(d.fn, (params, c1, step), monkeypatch)
        (d1, _), (d2, _) = dec[0](params, c1, step), dec[1](params, c2, step)
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


def test_named_scopes_leave_the_loss_bit_identical(monkeypatch):
    b, mesh = bundle("whisper-tiny", "train")
    with jax.set_mesh(mesh):
        state = init_train_state(b, jax.random.PRNGKey(0))
        batch = b.model.make_inputs(ShapeConfig("s", 32, 2, "train"), jax.random.PRNGKey(1))
        scoped, unscoped = _scoped_and_unscoped(b.fn, (state, batch), monkeypatch)
        (s1, m1), (s2, m2) = scoped(state, batch), unscoped(state, batch)
        np.testing.assert_array_equal(np.asarray(m1["loss"]), np.asarray(m2["loss"]))
        for x, y in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_pipeline_spans_reach_the_host_plane(tmp_path):
    """The producer's ``data.make_batch`` runs on a thread of its own; the
    consumer's ``data.queue_wait`` and ``data.to_device`` nest inside the
    caller's annotation, on its thread."""
    from jax.profiler import ProfileData

    pipeline = SyntheticLMPipeline(get_arch("whisper-tiny", smoke=True),
                                   ShapeConfig("s", 8, 2, "train"), PipelineConfig(seed=3))
    batches = iter(pipeline)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("cb:next_batch"):
            for _ in range(2):
                jax.block_until_ready(next(batches))
    finally:
        jax.profiler.stop_trace()
        batches.close()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    (host,) = [p for p in ProfileData.from_file(path).planes if p.name == "/host:CPU"]
    where = {}  # span name -> [(thread, start, end)]
    for thread, line in enumerate(host.lines):
        for ev in line.events:
            where.setdefault(ev.name, []).append((thread, ev.start_ns, ev.end_ns))
    ((outer_thread, lo, hi),) = where["cb:next_batch"]
    for name in ("repro:data.queue_wait", "repro:data.to_device"):
        assert len(where[name]) == 2
        assert all(t == outer_thread and lo <= s <= e <= hi for t, s, e in where[name])
    assert where["repro:data.make_batch"]
    assert all(t != outer_thread for t, _, _ in where["repro:data.make_batch"])


# ----------------------------------------------------------- the reduction


def test_scope_components_unwrap_transforms():
    assert scopes.scope_components(
        "jit(train_step)/transpose(jvp(encoder))/while/body/closed_call/checkpoint/"
        "rematted_computation/attn/sdpa/tanh") == (
        "train_step", "encoder", "while", "body", "closed_call", "checkpoint",
        "rematted_computation", "attn", "sdpa", "tanh")
    assert scopes.scope_components("jit(f)/transpose(jvp())/jvp()/x") == (
        "f", "jvp()", "jvp()", "x")
    assert scopes.scope_components("") == ("",)


def synthetic():
    """Two devices; device 1 runs the same work shifted by 0.5 s. On thread
    0 a unit holds a decode step and a queue wait; thread 1 (the producer)
    makes a batch over the idle gap."""
    ops = [("a", 0.0, 1.0), ("b", 0.5, 2.0), ("c", 4.0, 5.0), ("d", 5.0, 5.5)]
    paths = ["jit(decode_step)/while/body/attn/sdpa/dot_general",
              "jit(decode_step)/while/body/attn/sdpa/div", "jit(decode_step)/jvp(mlp)/dot",
              ""]
    shifted = [(n, s + 0.5, e + 0.5) for n, s, e in ops]
    return scopes.ScopedSummary(
        ops=[ops, shifted], scopes=[paths, paths],
        spans=[("cb:unit", 0.0, 6.0), ("cb:decode_step", 0.0, 3.0)],
        program_spans=[("repro:data.queue_wait", 3.0, 3.6, 0),
                       ("repro:data.make_batch", 2.0, 3.9, 1),
                       ("repro:data.make_batch", 5.0, 7.0, 1)],
        span_threads=(0,))


def test_busy_in_scope_program_spans_and_breakdown():
    s = synthetic()
    # sdpa: [0, 2) on device 0, [0.5, 2.5) on device 1, clipped to [0, 3)
    assert s.busy_in_scope("sdpa", "decode_step") == 2.0
    assert s.busy_in_scope("attn", "decode_step") == 2.0
    assert s.busy_in_scope("mlp", "unit") == 1.0  # jvp(mlp) counts as mlp
    assert s.busy_in_scope("mlp", "decode_step") == 0.0
    assert s.program_host_in("data.queue_wait") == pytest.approx(0.6)
    assert s.program_count("data.make_batch") == 1  # the second ends after the window
    assert s.program_host_in("data.make_batch") == pytest.approx(1.9)
    b = s.breakdown()
    # device 0's busy time by first model scope: b starts inside a, so a
    # keeps only [0, 0.5) as its own
    assert b["device_scopes"] == [["attn", 2.0], ["mlp", 1.0], ["unscoped", 0.5]]
    # idle [2, 4) on device 0: its middle, 3.0, lies in the queue wait on the
    # units' thread (the producer's make_batch there is on another thread)
    assert b["idle_gaps"] == [["unit", 2.5]]
    assert b["idle_gaps_inner"] == [["data.queue_wait", 2.0], ["unit", 0.5]]


def test_own_time_of_nested_operations():
    """A loop's event holds its body's events; ops that merely touch are not
    nested."""
    ops = [(0.0, 10.0), (1.0, 3.0), (3.0, 4.0), (3.5, 3.8), (10.0, 12.0), (11.0, 13.0)]
    assert scopes.own_time(ops) == pytest.approx([7.0, 2.0, 0.7, 0.3, 1.0, 2.0])


def test_summary_without_program_marks():
    """A trace of a program with no scopes or spans: every operation is
    unscoped and nothing is read from the program."""
    s = scopes.ScopedSummary(ops=[[("a", 0.0, 1.0)]], spans=[("cb:unit", 0.0, 2.0)])
    assert s.scopes == [[""]] and s.busy_in_scope("sdpa", "unit") == 0.0
    assert s.program_count("data.queue_wait") == 0
    assert s.breakdown()["device_scopes"] == [["unscoped", 1.0]]


def reading(summary, counts=None):
    return run.Reading(summary, counts or {}, run.peak_of("TPU v5 lite"), 1.5)


def read_metric(name, r):
    return run.load_module(ROOT / "chipbench" / "metrics" / f"{name}.py").read(r)


def test_new_metrics_read_the_program_marks():
    s = synthetic()
    s.spans += [("cb:next_batch", 3.0, 4.0), ("cb:train_step", 4.0, 6.0),
                ("cb:prefill", 0.0, 1.0)]
    r = reading(s)
    assert read_metric("sdpa_ms.decode", r) == pytest.approx(2e3)
    assert read_metric("sdpa_ms.prefill", r) == pytest.approx(750.0)  # (1 + 0.5) / 2
    assert read_metric("sdpa_ms.train", r) is None  # no sdpa operation in the train step
    assert read_metric("queue_wait_ms.train", r) == pytest.approx(600.0)
    assert read_metric("to_device_ms.train", r) is None
    assert read_metric("make_batch_ms.train", r) == pytest.approx(1900.0)


def test_new_metrics_are_silent_without_program_marks():
    s = scopes.ScopedSummary(ops=[[("a", 0.0, 1.0)]],
                             spans=[("cb:unit", 0.0, 9.0), ("cb:decode_step", 0.0, 2.0),
                                    ("cb:prefill", 2.0, 3.0), ("cb:train_step", 3.0, 4.0),
                                    ("cb:next_batch", 4.0, 5.0)])
    metrics = scopes.PROGRAM_METRICS
    assert {m: read_metric(m, reading(s)) for m in metrics} == dict.fromkeys(metrics)


# What trace.py reads from trace_small (at commit 0ddb004 as now), with these
# counts and a v5e's peaks.
SMALL_COUNTS = {"decode_bytes": 2e7, "decode_flops": 1e10, "train_flops": 2e10}
SMALL_READERS = {
    "compile_s": 1.5, "decode_step_ms": 0.0902123333333324,
    "hbm_roofline.decode": 9.023165502139276, "idle_share.serve": 97.3448603877897,
    "idle_share.train": 97.3448603877897, "input_wait_ms.train": None,
    "mfu.decode": 18.756275498101694, "mfu.prefill": None, "mfu.serve": 0.4980053005253922,
    "mfu.train": 0.9960106010507844, "prefill_ms": None, "train_step_ms": None,
}
SMALL_BREAKDOWN = {
    "device_ops": [["jit__lambda/%fusion", 0.0002705910000000006],
                   ["jit__lambda/%copy-start", 3.9000000000288804e-08],
                   ["jit__lambda/%copy-done", 6.999999996315509e-09]],
    "idle_gaps": [["unit", 0.0093830985], ["decode_step", 0.0005392125000000039]],
}


@pytest.mark.parametrize("reader", [trace.read_file, scopes.read_file])
def test_recorded_small_trace_reads_as_before(reader):
    """Read with the program's marks, every number trace.py gives stays."""
    s = reader(str(DATA / "trace_small.xplane.pb.gz"))
    assert (s.window_s, s.busy_s) == (0.010192948, 0.0002706369999999972)
    assert s.busy_in("decode_step") == 0.0002706369999999972
    assert s.host_in("decode_step") == 0.002636318999999998
    r = reading(s, SMALL_COUNTS)
    assert {m: read_metric(m, r) for m in SMALL_READERS} == SMALL_READERS
    b = s.breakdown()
    assert {k: b[k] for k in SMALL_BREAKDOWN} == SMALL_BREAKDOWN


def test_recorded_small_trace_scopes():
    """The three fusions carry the jit's scope path; the copies the compiler
    put in carry none, and read as unscoped."""
    s = scopes.read_file(str(DATA / "trace_small.xplane.pb.gz"))
    names = [n.rsplit("/", 1)[1] for n, _, _ in s.ops[0]]
    assert [sc for n, sc in zip(names, s.scopes[0]) if n == "%fusion"] == [
        "jit(<lambda>)/dot_general"] * 3
    assert {sc for n, sc in zip(names, s.scopes[0]) if n != "%fusion"} == {""}
    b = s.breakdown()
    assert b["device_scopes"] == [["unscoped", pytest.approx(s.busy_s)]]
    assert b["idle_gaps_inner"] == b["idle_gaps"] and s.program_spans == []


def test_recorded_scoped_trace():
    """A trace recorded on a TPU v5e by ``chipbench/tests/record_trace_scoped.py``:
    three units of a scanned two-layer program with ``attn/sdpa`` and
    ``mlp`` scopes, a ``repro:data.queue_wait`` span in each unit and
    ``repro:data.make_batch`` spans on a second thread. Every number is
    checked against a sum by hand over the file's events."""
    from jax.profiler import ProfileData

    path = DATA / "trace_scoped.xplane.pb.gz"
    s = scopes.read_file(str(path))
    base = trace.read_file(str(path))
    assert (s.ops, s.spans) == (base.ops, base.spans)
    assert {k: s.breakdown()[k] for k in ("device_ops", "idle_gaps")} == base.breakdown()

    ops = [(st, e, sc) for (_, st, e), sc in zip(s.ops[0], s.scopes[0])]
    assert any("/attn/sdpa/" in sc for _, _, sc in ops) and any("/mlp/" in sc for _, _, sc in ops)
    steps = [(a, b) for n, a, b in s.spans if n == "cb:decode_step"]
    assert len(steps) == 3

    def by_hand(component, windows):  # scoped operations run one at a time
        return sum(max(0.0, min(e, hi) - max(st, lo)) for st, e, sc in ops
                   if component in sc.split("/") for lo, hi in windows)

    for component in ("sdpa", "attn", "mlp"):
        assert by_hand(component, steps) > 0
        assert s.busy_in_scope(component, "decode_step") == pytest.approx(by_hand(component, steps))
    window = [(s.lo, s.hi)]
    device_scopes = dict(s.breakdown()["device_scopes"])
    assert device_scopes["attn"] == pytest.approx(by_hand("attn", window))
    assert device_scopes["mlp"] == pytest.approx(by_hand("mlp", window))
    # the layer loop's own event holds its body's: each operation counts its
    # own time, and together they cover the busy time once
    assert device_scopes["unscoped"] > 0
    assert sum(device_scopes.values()) == pytest.approx(s.busy_s)

    # the program's spans, as the host plane holds them, "#step=..." stripped
    (host,) = [p for p in ProfileData.from_serialized_xspace(gzip.open(path).read()).planes
               if p.name == "/host:CPU"]
    raw = [(t, ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
           for t, line in enumerate(host.lines) for ev in line.events
           if ev.name.startswith(scopes.PROGRAM_PREFIX)]
    assert [sp[:3] for sp in s.program_spans] == [(n.split("#")[0], a, b) for _, n, a, b in raw]
    waits = [(a, b) for n, a, b, t in s.program_spans if n == "repro:data.queue_wait"]
    makes = [sp for sp in s.program_spans if sp[0] == "repro:data.make_batch"]
    assert len(waits) == 3 and makes
    assert all(t in s.span_threads for n, _, _, t in s.program_spans if n.endswith("queue_wait"))
    assert not {t for *_, t in makes} & set(s.span_threads)
    assert s.program_count("data.queue_wait") == 3
    assert s.program_host_in("data.queue_wait") == pytest.approx(sum(b - a for a, b in waits))
    inside = [(a, b) for _, a, b, _ in makes if s.lo <= a and b <= s.hi]
    assert s.program_count("data.make_batch") == len(inside)

    # idle gaps whose middle lies in a queue wait are named by it; none by
    # the producer's spans
    merged, gaps, t = trace.union([(st, e) for st, e, _ in ops]), [], s.lo
    for st, e in merged + [(s.hi, s.hi)]:
        st, e = max(st, s.lo), min(e, s.hi)
        if st > t:
            gaps.append((t, st))
        t = max(t, e)
    in_wait = sum(b - a for a, b in gaps if any(lo <= (a + b) / 2 < hi for lo, hi in waits))
    inner = dict(s.breakdown()["idle_gaps_inner"])
    assert in_wait > 0 and inner["data.queue_wait"] == pytest.approx(in_wait)
    assert "data.make_batch" not in inner
