"""XLA attention vs naive oracle: shape/dtype/mask sweeps of the blockwise and
single-shot branches, dynamic (traced) sliding windows, decode path with
kv_length masking and with the step's own K/V given beside the cache,
grouped-query decode without a K/V repeat (lowered program, and under model
parallelism)."""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.models.attention import attention, attention_reference


def _mk(b, s, t, hq, hkv, dh, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, hq, dh), dtype)
    k = jax.random.normal(ks[1], (b, t, hkv, dh), dtype)
    v = jax.random.normal(ks[2], (b, t, hkv, dh), dtype)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    return q, k, v, pos


# (hq, hkv): g = 1, 2, 6 (6 is InternVL2's 48/8)
HEADS = [(4, 4), (4, 2), (12, 2)]
# (s, window, cap) of the single-shot branch (one query, or t <= block_kv)
SINGLE_SHOT = [
    (1, 0, 0.0),     # decode over part-filled caches (kv_length per row)
    (64, 0, 0.0),    # short causal prefill
    (64, 16, 0.0),   # sliding window
    (64, 0, 30.0),   # logit softcap
]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("b,s,hq,hkv,dh,window,cap", [
    (2, 128, 4, 2, 32, 0, 0.0),
    (1, 257, 4, 1, 64, 0, 0.0),      # odd length -> padded block path
    (2, 192, 8, 8, 32, 64, 0.0),     # sliding window (MHA)
    (1, 128, 4, 2, 32, 0, 30.0),     # logit softcap
    *[(2, s, hq, hkv, 32, w, c) for hq, hkv in HEADS for s, w, c in SINGLE_SHOT],
])
def test_blockwise_matches_reference(dtype, tol, b, s, hq, hkv, dh, window, cap):
    """Both XLA branches against the oracle, which repeats K/V to the
    query-head count: blockwise (t > block_kv = 64) and single-shot (one
    query, or t <= block_kv), whose query heads are grouped over their KV
    head. Self-attention (t = s), but a single query decodes over a 96-slot
    cache."""
    t = 96 if s == 1 else s
    q, k, v, pos = _mk(b, s, t, hq, hkv, dh, dtype)
    kv_len = None
    if s == 1:
        kv_len = jnp.asarray([60, 23], jnp.int32)
        pos = (kv_len - 1)[:, None]
    kw = dict(q_positions=pos, kv_length=kv_len, window=window, softcap_val=cap)
    out = attention(q, k, v, block_kv=64, **kw)
    ref = attention_reference(q, k, v, **kw)
    assert out.shape == q.shape and out.dtype == q.dtype
    assert jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))) < tol


def test_dynamic_window_matches_static():
    """A traced window scalar must behave exactly like the static value, and
    window<=0 must mean 'full' (the unified local/global stack contract)."""
    q, k, v, pos = _mk(2, 128, 128, 4, 2, 32, jnp.float32)
    static = attention(q, k, v, q_positions=pos, window=32, block_kv=64)
    dyn = jax.jit(
        lambda w: attention(q, k, v, q_positions=pos, window=w, block_kv=64)
    )(jnp.asarray(32, jnp.int32))
    assert jnp.max(jnp.abs(static - dyn)) < 1e-6
    full_static = attention(q, k, v, q_positions=pos, window=0, block_kv=64)
    full_dyn = jax.jit(
        lambda w: attention(q, k, v, q_positions=pos, window=w, block_kv=64)
    )(jnp.asarray(0, jnp.int32))
    assert jnp.max(jnp.abs(full_static - full_dyn)) < 1e-6


def test_decode_kv_length_mask():
    """Single-token decode against a partially-filled cache only sees the
    valid prefix."""
    b, t, hq, hkv, dh = 2, 64, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, 1, hq, dh))
    k = jax.random.normal(ks[1], (b, t, hkv, dh))
    v = jax.random.normal(ks[2], (b, t, hkv, dh))
    valid = 40
    pos = jnp.full((b, 1), valid - 1, jnp.int32)
    kv_len = jnp.full((b,), valid, jnp.int32)
    out = attention(q, k, v, q_positions=pos, kv_length=kv_len)
    # poisoning the masked-out tail must not change the result
    k2 = k.at[:, valid:].set(1e3)
    v2 = v.at[:, valid:].set(-1e3)
    out2 = attention(q, k2, v2, q_positions=pos, kv_length=kv_len)
    assert jnp.max(jnp.abs(out - out2)) < 1e-6


@pytest.mark.parametrize("hq,hkv", HEADS)
@pytest.mark.parametrize("window,cap", [(0, 0.0), (16, 0.0), (0, 30.0)])
def test_decode_new_kv_matches_a_written_cache(hq, hkv, window, cap):
    """The step's own K/V given beside the cache (``new_kv``) attends as the
    same K/V written into the cache at ``kv_length`` does; whatever the cache
    holds from that position on stays unseen."""
    b, t, dh, pos = 2, 64, 32, 40
    q, k, v, _ = _mk(b, 1, t, hq, hkv, dh, jnp.float32, seed=5)
    k_new, v_new = (x[:, pos:pos + 1] for x in _mk(b, 1, t, hq, hkv, dh, jnp.float32, seed=6)[1:3])
    qpos = jnp.full((b, 1), pos, jnp.int32)
    kw = dict(q_positions=qpos, window=window, softcap_val=cap)
    written = attention(q, k.at[:, pos:pos + 1].set(k_new), v.at[:, pos:pos + 1].set(v_new),
                        kv_length=jnp.full((b,), pos + 1, jnp.int32), **kw)
    beside = attention(q, k.at[:, pos:].set(1e3), v.at[:, pos:].set(-1e3),
                       kv_length=jnp.full((b,), pos, jnp.int32), new_kv=(k_new, v_new), **kw)
    assert jnp.max(jnp.abs(written - beside)) < 2e-5


@pytest.mark.parametrize("s,t,causal,hq,hkv,window", [
    # padded last block, causal self-attention
    pytest.param(257, 257, True, 4, 2, 0, id="257-257-True"),
    # cross-attention: queries over a longer encoder
    pytest.param(96, 200, False, 4, 2, 0, id="96-200-False"),
    # single-shot (t <= block_kv), grouped, with a sliding window
    pytest.param(64, 64, True, 4, 2, 16, id="64-64-True-g2"),
    pytest.param(64, 64, True, 12, 2, 16, id="64-64-True-g6"),
])
def test_blockwise_gradient_matches_reference(s, t, causal, hq, hkv, window):
    """The XLA path's gradients equal the naive oracle's, in the blockwise
    branch and in the grouped single-shot branch that short-context training
    runs. The blockwise backward never differentiates its running max: that
    derivative divides by the count of scores equal to the max, which is
    zero — NaN gradients — whenever the backward pass recomputes bf16 scores
    with other rounding (as XLA does on TPU)."""
    q, k, v, pos = _mk(1, s, t, hq, hkv, 32, jnp.float32)

    def loss(fn, **kw):
        return lambda q, k, v: jnp.sum(jnp.sin(
            fn(q, k, v, q_positions=pos, causal=causal, window=window, **kw)))

    xla = loss(attention, block_kv=64)
    got = jax.grad(xla, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(attention_reference), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert jnp.max(jnp.abs(g - w)) < 1e-4
    if t > 64:
        # the max's derivative compares every score with it: a score-shaped eq
        grad_jaxpr = str(jax.make_jaxpr(jax.grad(xla))(q, k, v))
        assert not re.search(r"bool\[\d+,\d+,\d+,\d+\] = eq ", grad_jaxpr)


@pytest.mark.parametrize("hq,hkv,s,t", [
    (12, 2, 1, 96),    # decode, g = 6
    (48, 8, 1, 96),    # decode at InternVL2's heads
    (12, 2, 32, 64),   # short prefill (t <= block_kv)
])
def test_decode_reads_each_kv_head_once(hq, hkv, s, t):
    """The single-shot branch's lowered program holds no K/V-sized tensor
    with Hq heads, nor the (Hkv, G) broadcast a repeat goes through: each KV
    head is read as it is by its group of G query heads."""
    b, dh = 2, 32
    q, k, v, pos = _mk(b, s, t, hq, hkv, dh, jnp.bfloat16)
    kv_len = None
    if s == 1:
        kv_len = jnp.asarray([60, 23], jnp.int32)
        pos = (kv_len - 1)[:, None]
    text = jax.jit(lambda q, k, v: attention(
        q, k, v, q_positions=pos, kv_length=kv_len)).lower(q, k, v).as_text()
    g = hq // hkv
    assert f"tensor<{b}x{t}x{hq}x{dh}x" not in text
    assert f"tensor<{b}x{t}x{hkv}x{g}x{dh}x" not in text
    assert f"tensor<{b}x{hkv}x{g}x{s}x{t}xf32>" in text  # grouped scores


@pytest.mark.parametrize("hq,hkv,partition", [
    (8, 4, "heads"),      # K/V and q heads split over the model axis
    (12, 2, "sequence"),  # Hkv % mp != 0: the cache timeline is split
])
def test_grouped_decode_under_model_parallelism(subproc, hq, hkv, partition):
    """One decode step of a small GQA model at mp=4 (fake CPU devices)
    compiles and gives the logits of the same step on one device."""
    out = subproc(f"""
import dataclasses
import jax, jax.numpy as jnp
from repro.configs.archs import get_arch
from repro.configs.base import RunConfig, ShapeConfig
from repro.distributed.steps import make_decode_step
from repro.launch.mesh import make_host_mesh
arch = dataclasses.replace(get_arch("llama3.2-1b", smoke=True),
                           num_heads={hq}, num_kv_heads={hkv}, head_dim=16)
shape = ShapeConfig("d", 32, 2, "decode")
logits = {{}}
for mp in (1, 4):
    mesh = make_host_mesh(model_parallel=mp, devices=jax.devices()[:mp])
    with jax.set_mesh(mesh):
        step = make_decode_step(
            arch, RunConfig(mesh_model_parallel=mp, kv_partition="{partition}"),
            shape, mesh)
        params = step.model.init_params(jax.random.PRNGKey(0))
        keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
        caches = jax.tree.map(
            lambda a: jax.random.normal(next(keys), a.shape, a.dtype),
            step.abstract_inputs[1])
        batch = {{"tokens": jnp.asarray([[5], [7]], jnp.int32),
                  "cache_len": jnp.asarray(20, jnp.int32)}}
        placed = step.place(mesh, params, caches, batch)
        logits[mp] = jax.device_get(step.jit(donate=False)(*placed)[0])
err = float(jnp.max(jnp.abs(logits[4].astype(jnp.float32) - logits[1].astype(jnp.float32))))
scale = float(jnp.max(jnp.abs(logits[1].astype(jnp.float32))))
print("ERR", err, scale)
assert err <= 2e-2 * scale, (err, scale)
print("GROUPED_MP_OK")
""", devices=4)
    assert "GROUPED_MP_OK" in out
