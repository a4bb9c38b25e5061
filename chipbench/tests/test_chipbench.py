"""The benchmark's own tests, on the CPU at small sizes: the trace reduction,
the analytic counts, the window arithmetic, the references against the
program, the control and the planted faults, discovery by file, and the
refusal to run without a TPU.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

Nothing here loads the TPU runtime: the program runs on the CPU backend,
and the trace test reads a trace recorded on a TPU v5e.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import flops, gen, run, trace  # noqa: E402
from chipbench.reference import dense_lm  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

# the registry's smoke sizes of the two architectures
IVL = {"name": "ivl-smoke", "arch": "internvl2-26b", "smoke": True, "hidden_size": 64,
       "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 16, "intermediate_size": 128, "vocab_size": 512, "rope_theta": 10000.0,
       "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
       "run": {"mesh_model_parallel": 1}}
WH = {"name": "wh-smoke", "arch": "whisper-tiny", "smoke": True, "hidden_size": 64,
      "encoder_layers": 2, "decoder_layers": 2, "num_attention_heads": 4,
      "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 128, "vocab_size": 512,
      "max_source_positions": 12, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
      "tie_word_embeddings": False,
      "optimizer": {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
                    "peak_lr": 3e-4, "warmup_steps": 100, "clip_global_norm": 1.0},
      "run": {"mesh_model_parallel": 1}}
SERVE = {"engine": "fixed_batch_serve", "batch": 4, "prompt_len": 16, "image_tokens": 9,
         "max_new": 16, "check_requests": 4, "check_rows_per_block": 2,
         "trace": {"first_unit": 1, "units": 1}}
TRAIN = {"engine": "train_steps", "batch": 4, "target_len": 8, "check_steps": 3,
         "check_rows_per_block": 2, "trace": {"first_unit": 1, "units": 2}}
# Limits at this size, set from readings of this file's seeds as the cells'
# limits are (PERF.md): program readings below, control and faults above.
SERVE_LIMITS = {"served_logit_gap": 0.03}
TRAIN_LIMITS = {"loss_gap": 0.01, "grad_leaf_gap": 0.01, "change_leaf_gap": 0.01}
SEEDS = (3, 2**31 + 11)


def cell(name, config, traffic, limits):
    return run.Cell(name=name, chips=1, config=config, traffic=traffic, limits=limits,
                    end_to_end=[m for m in BENCH["end_to_end"] if run._applies(m, name)],
                    per_layer=[])


def serve_cell():
    return cell("ivl2-docqa", IVL, SERVE, SERVE_LIMITS)


def train_cell():
    return cell("whisper-train", WH, TRAIN, TRAIN_LIMITS)


def engine(name):
    """The engine module the harness itself loads."""
    return run.load_module(ROOT / "chipbench" / "engines" / f"{name}.py")


def run_on_cpu(c, seed, seconds=0.0, **kw):
    return run.run_cell(c, seed, seconds, False, jax.devices("cpu"),
                        t_start=time.perf_counter(), log=lambda *_: None, **kw)


# ------------------------------------------------------------- trace reduction


def test_union_and_busy_inside_spans():
    s = trace.Summary(
        ops=[[("a", 0.0, 1.0), ("b", 0.5, 2.0), ("c", 4.0, 5.0)]],
        spans=[("cb:unit", 0.0, 6.0), ("cb:decode_step", 0.0, 3.0),
               ("cb:decode_step", 3.0, 6.0), ("cb:argmax_sync", 2.5, 3.0)])
    assert s.window_s == 6.0
    assert s.busy_s == 3.0  # [0, 2) and [4, 5)
    assert s.busy_in("decode_step") == 3.0 and s.count("decode_step") == 2
    assert s.host_in("decode_step") == 6.0
    b = s.breakdown()
    assert b["device_ops"][0] == ["b", 1.5]
    # idle [2, 4) splits at its middle, 3.0, inside the second decode span;
    # [5, 6) also in the second
    assert dict(map(tuple, b["idle_gaps"])) == {"decode_step": 3.0}


def test_recorded_trace():
    """A trace recorded on a TPU v5e: three units, each one decode_step span
    around one jitted program, a 2 ms host sleep between units. The device
    clock runs about a millisecond behind the host's; once aligned, every
    device operation falls inside a decode_step span."""
    import gzip

    from jax.profiler import ProfileData

    with gzip.open(DATA / "trace_small.xplane.pb.gz") as f:
        raw = ProfileData.from_serialized_xspace(f.read())
    planes = {p.name: p for p in raw.planes}
    dev, host = planes["/device:TPU:0"], planes["/host:CPU"]
    modules = sorted((e.start_ns, e.end_ns) for line in dev.lines
                     if line.name == "XLA Modules" for e in line.events)
    host_ev = [e for line in host.lines for e in line.events]
    launches = sorted(e.start_ns for e in host_ev if e.name == "tpu::System::Execute")
    done = sorted(e.start_ns for e in host_ev if e.name == "CompleteCallbacks")
    units = [(e.start_ns, e.end_ns) for e in host_ev if e.name == "cb:unit"]
    assert len(modules) == len(launches) == len(done) == len(units) == 3

    shift = trace.clock_offset_ns(dev, host)
    assert max(l - m[0] for l, m in zip(launches, modules)) <= shift
    assert shift <= min(d - m[1] for d, m in zip(done, modules))

    s = trace.read_file(str(DATA / "trace_small.xplane.pb.gz"))
    assert s.count("unit") == 3 and s.count("decode_step") == 3
    lo, hi = min(u[0] for u in units), max(u[1] for u in units)
    assert s.window_s == pytest.approx((hi - lo) * 1e-9)
    module_s = sum(e - b for b, e in modules) * 1e-9
    assert 0.9 * module_s <= s.busy_s <= module_s
    assert s.busy_in("decode_step") == pytest.approx(s.busy_s)
    b = s.breakdown()
    assert b["device_ops"][0][0] == "jit__lambda/%fusion"
    assert {name for name, _ in b["idle_gaps"]} <= {"unit", "decode_step"}


# ------------------------------------------------------------- analytic counts


def test_flops_against_hand_counts():
    # smoke internvl2: d 64, 4 q heads and 2 kv heads of 16, ff 128, vocab 512
    attn = 2 * 64 * 64 + 2 * 64 * 32  # q and o, k and v
    layer = attn + 3 * 64 * 128
    assert flops.layer_params(IVL) == layer == 36864
    # prefill of 1 x 16 over 2 layers: 2*params*tokens + causal attention
    # 4*Hq*Dh*(1+...+16) per layer + the head once
    assert flops.prefill_flops(IVL, 1, 16) == 2 * 2 * layer * 16 + 2 * 4 * 4 * 16 * 136 + 2 * 64 * 512
    # decode writing position 16 attends to 17 positions
    assert flops.decode_flops(IVL, 2, 16) == 2 * (2 * 2 * layer + 2 * 4 * 4 * 16 * 17 + 2 * 64 * 512)
    # bytes: weights + norms + head + 2 embedding rows, bf16; KV of 17 positions
    w = 2 * (layer + 128) + 64 + 512 * 64 + 2 * 64
    kv = 2 * 2 * 2 * 2 * 16 * 17
    assert flops.decode_bytes(IVL, 2, 16) == 2 * w + 2 * kv
    # whisper smoke, 12 frames, 8 targets
    a = 2 * 64 * 64 * 2
    enc = 2 * (2 * 12 * (a + 3 * 64 * 128) + 4 * 4 * 16 * 144)
    dec = 2 * (2 * 8 * (a + 3 * 64 * 128) + 4 * 4 * 16 * 36)
    cross = 2 * (2 * 8 * 2 * 64 * 64 + 2 * 12 * 2 * 64 * 64 + 4 * 4 * 16 * 96)
    assert flops.encdec_forward_flops(WH, 8) == enc + dec + cross + 2 * 8 * 64 * 512
    assert flops.train_flops(WH, 4, 8) == 12 * flops.encdec_forward_flops(WH, 8)


# --------------------------------------------------------- window arithmetic


def test_quantile_matches_numpy():
    fbs = engine("fixed_batch_serve")

    rng = np.random.default_rng(0)
    for n in (1, 2, 7, 100):
        v = rng.random(n).tolist()
        for q in (0.0, 0.5, 0.9, 0.95, 1.0):
            assert fbs.quantile(v, q) == pytest.approx(float(np.quantile(v, q)))


def test_injected_stall_moves_itl_and_rate():
    """The same engine over two windows of four batches, the second with a
    40 ms stall planted in every third decode step: the tail and the rate
    both move, and by what the stall explains."""
    fbs = engine("fixed_batch_serve")

    c = serve_cell()
    clock = time.perf_counter
    mesh = cpu_mesh()
    with jax.set_mesh(mesh):
        eng = fbs.Engine(c, 5, mesh, clock)
        n = 4

        def window():
            eng.itl.clear()
            eng.tokens_out = 0
            t0 = clock()
            for _ in range(n):
                eng.unit()
            return eng.end_to_end(clock() - t0)

        base = window()
        calls = {"n": 0}
        decode = eng.decode_fn

        def stalled(*a):
            calls["n"] += 1
            if calls["n"] % 3 == 0:
                time.sleep(0.04)
            return decode(*a)

        eng.decode_fn = stalled
        slow = window()
    assert slow["itl_p95_ms"] > base["itl_p95_ms"] + 30
    assert slow["out_tok_s"] < base["out_tok_s"]
    steps = n * (SERVE["max_new"] - 1)
    assert len(eng.itl) == steps
    tokens = n * SERVE["batch"] * SERVE["max_new"]
    assert tokens / slow["out_tok_s"] > (steps // 3) * 0.04


def cpu_mesh():
    from repro.launch.mesh import make_host_mesh

    return make_host_mesh(1, devices=jax.devices("cpu")[:1])


# -------------------------------------------- references against the program


def test_weights_drawn_per_layer_match_the_stacked_tree():
    key = gen.seed_key(2**33 + 1)
    abstract = {"stack": {"l0": {"w": jax.ShapeDtypeStruct((3, 4, 5), jnp.bfloat16)}},
                "embed": jax.ShapeDtypeStruct((6, 4), jnp.float32)}
    t = gen.weights(key, abstract)
    for i in range(3):
        assert jnp.array_equal(t["stack"]["l0"]["w"][i],
                               gen.draw_layer(key, "stack/l0/w", i, (4, 5), jnp.bfloat16))
    assert jnp.array_equal(t["embed"], gen.draw(key, "embed", (6, 4), jnp.float32))


@pytest.mark.parametrize("seed", SEEDS)
def test_serving_matches_the_reference(seed):
    """Prefill then decode through the grown cache, greedy, against the
    reference's full forward over prompt and served tokens."""
    out = run_on_cpu(serve_cell(), seed, seconds=0.2)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= SERVE["batch"]
    footprints = out["device"]["program_bytes"]
    assert set(footprints) == {"prefill", "decode"}
    assert out["device"]["memory_peak_bytes"] >= max(footprints.values()) > 0


@pytest.mark.parametrize("seed", SEEDS)
def test_training_matches_the_reference(seed):
    """Three steps: losses, first clipped gradient and change per leaf."""
    out = run_on_cpu(train_cell(), seed, seconds=0.2)
    assert out["correct"], out["checks"]
    assert out["metrics"]["train_tok_s"]["value"] > 0


def test_dense_reference_is_causal_and_follows_the_prompt():
    """Logits at a position do not depend on later tokens."""
    key = gen.seed_key(9)
    ref = dense_lm.DenseLM(IVL, key)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 512, (1, 20)), jnp.int32)
    patches = gen.patches(key, jnp.arange(1, dtype=jnp.int32), 9, 64)
    a, b = ref.logits([(toks, patches), (toks.at[0, 15:].set(1), patches)], 0)
    np.testing.assert_allclose(a[0, :15], b[0, :15], rtol=1e-6, atol=1e-6)
    assert not np.allclose(a[0, 15:], b[0, 15:])


# ------------------------------------------------------- control and faults


@pytest.mark.parametrize("seed", SEEDS)
def test_serving_control_fails(seed):
    """The reference in float8, put in the program's place, reads over the
    limit, and a run that checks it comes out not correct."""
    from chipbench import calibrate

    r = calibrate.read_seed(serve_cell(), seed, 0.0, jax.devices("cpu"))
    assert r["program"] <= SERVE_LIMITS["served_logit_gap"] < r["control"]
    out = run_on_cpu(serve_cell(), seed, control=True)
    assert not out["correct"]
    assert out["checks"]["served_logit_gap"]["value"] > SERVE_LIMITS["served_logit_gap"]


@pytest.mark.parametrize("seed", SEEDS)
def test_training_control_and_faults_fail(seed):
    from chipbench import calibrate

    r = calibrate.read_seed(train_cell(), seed, 0.0, jax.devices("cpu"))
    assert all(r["program"][k] <= lim for k, lim in TRAIN_LIMITS.items()), r["program"]
    for reading in ("control", "half_batch", "labels_shifted"):
        assert any(r[reading][k] > lim for k, lim in TRAIN_LIMITS.items()), (reading, r[reading])
    assert not run_on_cpu(train_cell(), seed, control=True)["correct"]


def test_check_sample_spreads_over_slots_and_batches():
    fbs = engine("fixed_batch_serve")

    for seed in SEEDS:
        rng = np.random.default_rng((seed, 1))
        picked = fbs.check_sample(list(range(32)), 8, 8, rng)  # 4 batches of 8
        assert len(set(picked)) == 8
        assert sorted(r % 8 for r in picked) == list(range(8))  # every slot once
        assert {r // 8 for r in picked} == set(range(4))  # every batch
    assert fbs.check_sample(list(range(5)), 1, 3, np.random.default_rng(0)) != []
    assert len(fbs.check_sample(list(range(5)), 1, 9, np.random.default_rng(0))) == 5


def test_served_token_altered_is_not_correct(monkeypatch):
    fbs = engine("fixed_batch_serve")

    real = fbs.Engine.__init__

    def init(self, *a, **kw):
        real(self, *a, **kw)
        greedy, calls = self.greedy, {"n": 0}

        def altered(logits):
            calls["n"] += 1
            tok = greedy(logits)
            return tok.at[0, 0].add(1) if calls["n"] == 7 else tok

        self.greedy = altered

    monkeypatch.setattr(fbs.Engine, "__init__", init)
    out = run_on_cpu(serve_cell(), SEEDS[0])
    assert not out["correct"]


def _train_fault(monkeypatch, wrap_step=None, wrap_batch=None):
    ts = engine("train_steps")

    real = ts.Engine.__init__

    def init(self, cell, seed, mesh, clock):
        import repro.data.pipeline as pl

        if wrap_batch is not None:
            orig = pl.SyntheticLMPipeline._host_batch
            monkeypatch.setattr(pl.SyntheticLMPipeline, "_host_batch",
                                lambda s, step: wrap_batch(orig(s, step)))
        if wrap_step is not None:
            orig_jit = ts.make_train_step

            def make(*a, **kw):
                bundle = orig_jit(*a, **kw)
                jitted = bundle.jit()
                bundle.jit = lambda: wrap_step(jitted)
                return bundle

            monkeypatch.setattr(ts, "make_train_step", make)
        real(self, cell, seed, mesh, clock)

    monkeypatch.setattr(ts.Engine, "__init__", init)
    return run_on_cpu(train_cell(), SEEDS[0])


def test_train_state_unchanged_is_not_correct(monkeypatch):
    def unchanged(step):
        return lambda state, batch: (state, step(jax.tree.map(jnp.copy, state), batch)[1])

    assert not _train_fault(monkeypatch, wrap_step=unchanged)["correct"]


def test_train_half_batch_is_not_correct(monkeypatch):
    def half(b):
        n = len(b["tokens"]) // 2
        return {k: np.concatenate([v[:n], v[:n]]) for k, v in b.items()}

    assert not _train_fault(monkeypatch, wrap_batch=half)["correct"]


def test_train_labels_altered_is_not_correct(monkeypatch):
    def shifted(b):
        return dict(b, labels=np.roll(b["labels"], 1, axis=1))

    assert not _train_fault(monkeypatch, wrap_batch=shifted)["correct"]


# ------------------------------------------------------- driven by files


def test_config_traffic_and_metric_added_as_files(tmp_path, monkeypatch):
    """A cell and a per-layer metric that exist only as new files and
    entries are found and run; the metric is read from a (recorded) trace."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (tmp_path / "chipbench/configs/ivl-smoke.json").write_text(json.dumps(IVL))
    (tmp_path / "chipbench/traffic/tiny.json").write_text(json.dumps(SERVE))
    (tmp_path / "chipbench/limits/tiny-serve.json").write_text(json.dumps(SERVE_LIMITS))
    (tmp_path / "chipbench/metrics/units_traced.py").write_text(
        "def read(r):\n    return float(r.trace.count('unit'))\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "ivl-smoke", "source": "smoke", "reduced": [],
                             "file": "chipbench/configs/ivl-smoke.json"})
    bench["workloads"].append({"name": "tiny-serve", "config": "ivl-smoke",
                               "traffic": "tiny", "chips": 1, "why": "test"})
    bench["per_layer"] = [{"name": "units_traced", "unit": "1", "better": "higher",
                           "source": "device_trace", "layer": "test", "moves": "out_tok_s",
                           "workloads": ["tiny-serve"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    c = run.load_cell("tiny-serve", root=tmp_path)
    assert c.config == IVL and c.traffic == SERVE and c.root == tmp_path
    assert [m["name"] for m in c.end_to_end] == ["setup_s"]

    recorded = trace.read_file(str(DATA / "trace_small.xplane.pb.gz"))

    class Recorded:
        def start(self):
            pass

        def stop(self):
            return recorded

    monkeypatch.setattr(trace, "Tracer", Recorded)
    monkeypatch.setattr(run, "peak_of", lambda kind: {"bf16_flops_per_s": 1.0,
                                                      "hbm_bytes_per_s": 1.0})
    out = run.run_cell(c, 4, 0.0, True, jax.devices("cpu"), t_start=time.perf_counter(),
                       log=lambda *_: None)
    assert out["metrics"] == {"units_traced": {"value": 3.0, "unit": "1"}}
    assert out["device"]["busy_s"] == recorded.busy_s
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


# ------------------------------------------------------------ no chip, no run


def test_run_refuses_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "chipbench" / "run.py"),
                        "--workload", "ivl2-caption", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
