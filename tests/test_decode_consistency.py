"""Cache-correctness integration tests: prefill(N) + K decode steps must match
a single prefill over N+K tokens, for every architecture family (KV caches,
RWKV states, Mamba conv/ssm caches, whisper cross-attention caches), in the
logits and in every cache leaf; and the layer scan's decode must write the
same caches as the unrolled stack's."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import ARCH_NAMES, get_arch
from repro.configs.base import RunConfig

B, N, K = 2, 12, 4


def _pad_cache(caches, extra):
    def pad_leaf(path, x):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in ("k", "v", "ks", "vs"):
            pad = [(0, 0)] * x.ndim
            pad[2] = (0, extra)  # (groups, B, T, ...)
            return jnp.pad(x, pad)
        return x

    return jax.tree_util.tree_map_with_path(pad_leaf, caches)


def _inputs(arch, length):
    """(tokens (B, length), frontend inputs) drawn for ``arch``."""
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, length), 0, arch.vocab_size, jnp.int32)
    extras = {}
    if arch.frontend == "vision":
        extras["patches"] = 0.02 * jax.random.normal(jax.random.PRNGKey(3), (B, arch.frontend_seq, arch.d_model))
    elif arch.frontend == "audio":
        extras["frames"] = 0.02 * jax.random.normal(jax.random.PRNGKey(3), (B, arch.frontend_seq, arch.d_model))
    return toks, extras


def _decode(step, params, caches, toks, steps):
    """``steps`` decode steps of ``toks[:, N:]`` from prefill(N)'s caches
    (padded for them): (logits, caches) after each."""
    caches = _pad_cache(caches, steps)
    out = []
    for i in range(steps):
        batch = {"tokens": toks[:, N + i : N + i + 1],
                 "cache_len": jnp.asarray(N + i, jnp.int32)}
        logits, caches = step(params, caches, batch)
        out.append((logits, caches))
    return out


def _run(name, run: RunConfig, tol: float):
    from repro.models.model import Model

    arch = get_arch(name, smoke=True)
    if arch.num_experts:
        arch = dataclasses.replace(arch, moe_capacity_factor=64.0)  # no drops
    m = Model(arch, run)
    params = m.init_params(jax.random.PRNGKey(1))
    toks, extras = _inputs(arch, N + K)

    full_logits, _ = m.prefill(params, {"tokens": toks, **extras})
    _, caches = m.prefill(params, {"tokens": toks[:, :N], **extras})
    logits, _ = _decode(m.decode_step, params, caches, toks, K)[-1]
    err = float(jnp.max(jnp.abs(full_logits - logits)))
    assert err < tol, (name, err)


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_decode_matches_prefill(name):
    # rwkv/mamba: chunked-parallel vs step recurrence differ by f32 noise
    tol = 5e-2 if name in ("rwkv6-7b", "jamba-1.5-large-398b") else 2e-3
    _run(name, RunConfig(), tol)


@pytest.mark.parametrize("name", ["llama3.2-1b", "gemma2-9b"])
def test_decode_matches_prefill_int8_kv(name):
    """int8 KV caches trade accuracy for 2× cache capacity — still close."""
    _run(name, RunConfig(kv_cache_dtype="int8"), tol=0.35)


def _decode_both_ways(arch, kv_dtype: str, steps: int = 3):
    """Prefill once, then ``steps`` decode steps from the same caches with the
    layer scan and unrolled, in float32 compute: (logits, caches) of each,
    step by step."""
    from repro.models.model import Model

    run = RunConfig(kv_cache_dtype=kv_dtype, compute_dtype="float32")
    scanned, unrolled = Model(arch, run), Model(arch, run.replace(scan_layers=False))
    params = scanned.init_params(jax.random.PRNGKey(1))
    toks, extras = _inputs(arch, N + steps)
    _, caches = scanned.prefill(params, {"tokens": toks[:, :N], **extras})
    return tuple(_decode(jax.jit(m.decode_step), params, caches, toks, steps)
                 for m in (scanned, unrolled))


# every kind of cache leaf a decode step writes: bf16 K/V rows (dense GQA),
# mamba conv/ssm states beside K/V (jamba, two groups of its period 8), rwkv
# states, int8 K/V with their ks/vs scales, and whisper's read-only cross K/V
SCAN_CASES = {
    "dense_gqa": ("llama3.2-1b", {}, "bfloat16"),
    "mamba_hybrid": ("jamba-1.5-large-398b", {"num_layers": 16, "moe_capacity_factor": 64.0}, "bfloat16"),
    "rwkv": ("rwkv6-7b", {}, "bfloat16"),
    "int8_kv": ("llama3.2-1b", {}, "int8"),
    "cross_attention": ("whisper-tiny", {}, "bfloat16"),
}


@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_scanned_decode_matches_unrolled(case):
    """The layer scan writes each step's rows and states into the stacked
    caches as the unrolled stack does: the same logits and the same cache
    tree, step by step, bit for bit in float32 compute (in bfloat16 the two
    programs round a few products differently)."""
    name, changes, kv_dtype = SCAN_CASES[case]
    arch = dataclasses.replace(get_arch(name, smoke=True), **changes)
    scanned, unrolled = _decode_both_ways(arch, kv_dtype)
    for (logits_s, caches_s), (logits_u, caches_u) in zip(scanned, unrolled):
        np.testing.assert_array_equal(np.asarray(logits_s), np.asarray(logits_u))
        assert jax.tree.structure(caches_s) == jax.tree.structure(caches_u)
        for a, b in zip(jax.tree.leaves(caches_s), jax.tree.leaves(caches_u)):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_decode_cache_matches_prefill_cache(case):
    """What decode writes is what a prefill over the same tokens emits: after
    prefill(N) and K decode steps, every cache leaf (K/V rows, int8 scales,
    mamba and rwkv states, cross K/V) matches prefill(N + K)'s. The relative
    gap of a leaf is at most 0.026 (mamba's chunked scan against its step
    recurrence; bfloat16 rows 0.002-0.006); rows written one position off or
    states left as they were read 0.25-1.6."""
    from repro.models.model import Model

    name, changes, kv_dtype = SCAN_CASES[case]
    arch = dataclasses.replace(get_arch(name, smoke=True), **changes)
    m = Model(arch, RunConfig(kv_cache_dtype=kv_dtype, compute_dtype="float32"))
    params = m.init_params(jax.random.PRNGKey(1))
    toks, extras = _inputs(arch, N + K)
    _, want = m.prefill(params, {"tokens": toks, **extras})
    _, caches = m.prefill(params, {"tokens": toks[:, :N], **extras})
    _, caches = _decode(jax.jit(m.decode_step), params, caches, toks, K)[-1]
    got_leaves = jax.tree_util.tree_flatten_with_path(caches)[0]
    assert jax.tree.structure(caches) == jax.tree.structure(want)
    for (path, got), ref in zip(got_leaves, jax.tree.leaves(want)):
        got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
        gap = np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-30)
        assert gap < 0.1, (case, jax.tree_util.keystr(path), gap)


# decode's q/k/v projections end in an optimization barrier, which keeps the
# TPU compiler from re-laying their weights out; these are the attention forms
# that pass through it: dense GQA, int8 K/V, q/k/v biases, and whisper's
# cross-attention query beside its cached encoder K/V
BARRIER_CASES = {
    "dense_gqa": ("llama3.2-1b", "bfloat16"),
    "int8_kv": ("llama3.2-1b", "int8"),
    "qkv_bias": ("qwen2-72b", "bfloat16"),
    "cross_attention": ("whisper-tiny", "bfloat16"),
}


def _bundle(make, name, kind, kv_dtype="bfloat16"):
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(1, devices=jax.devices()[:1])
    with jax.set_mesh(mesh):
        return make(get_arch(name, smoke=True), RunConfig(kv_cache_dtype=kv_dtype),
                    ShapeConfig("s", 32, 2, kind), mesh), mesh


@pytest.mark.parametrize("case", sorted(BARRIER_CASES))
def test_decode_barrier_changes_no_number(case, monkeypatch):
    """``make_decode_step``'s logits and caches are the same, bit for bit in
    bfloat16 compute, with the projections' barrier and without it."""
    from repro.distributed.steps import make_decode_step

    name, kv_dtype = BARRIER_CASES[case]
    step, mesh = _bundle(make_decode_step, name, "decode", kv_dtype)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))

    def draw(a):
        if jnp.issubdtype(a.dtype, jnp.integer):
            return jax.random.randint(next(keys), a.shape, -127, 128, a.dtype)
        return jax.random.normal(next(keys), a.shape, a.dtype)

    params = step.model.init_params(jax.random.PRNGKey(0))
    caches = jax.tree.map(draw, step.abstract_inputs[1])
    batch = {"tokens": jnp.asarray([[5], [7]], jnp.int32),
             "cache_len": jnp.asarray(20, jnp.int32)}

    def run():
        fn = jax.jit(lambda *a: step.fn(*a))  # a fresh trace on each call
        with jax.set_mesh(mesh):
            text = fn.lower(params, caches, batch).as_text()
            return text, fn(params, caches, batch)

    text, (logits, out_caches) = run()
    with monkeypatch.context() as m:
        m.setattr(jax.lax, "optimization_barrier", lambda x: x)
        plain_text, (plain_logits, plain_caches) = run()
    assert "optimization_barrier" in text and "optimization_barrier" not in plain_text
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(plain_logits))
    assert jax.tree.structure(out_caches) == jax.tree.structure(plain_caches)
    for a, b in zip(jax.tree.leaves(out_caches), jax.tree.leaves(plain_caches)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kind", ["prefill", "train"])
@pytest.mark.parametrize("case", sorted(BARRIER_CASES))
def test_prefill_and_train_lower_without_the_barrier(case, kind, monkeypatch):
    """Only decode takes the barrier: prefill and train lower to the same
    program whether ``optimization_barrier`` is real or the identity."""
    from repro.distributed.steps import make_prefill_step, make_train_step

    name, kv_dtype = BARRIER_CASES[case]
    make = {"prefill": make_prefill_step, "train": make_train_step}[kind]
    step, mesh = _bundle(make, name, kind, kv_dtype)

    def lowered():
        with jax.set_mesh(mesh):
            return jax.jit(lambda *a: step.fn(*a)).lower(*step.abstract_inputs).as_text()

    text = lowered()
    with monkeypatch.context() as m:
        m.setattr(jax.lax, "optimization_barrier", lambda x: x)
        assert lowered() == text
