"""XLA blockwise attention vs naive oracle: shape/dtype/mask sweeps, dynamic
(traced) sliding windows, decode path with kv_length masking."""
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


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("b,s,hq,hkv,dh,window,cap", [
    (2, 128, 4, 2, 32, 0, 0.0),
    (1, 257, 4, 1, 64, 0, 0.0),      # odd length -> padded block path
    (2, 192, 8, 8, 32, 64, 0.0),     # sliding window (MHA)
    (1, 128, 4, 2, 32, 0, 30.0),     # logit softcap
])
def test_blockwise_matches_reference(dtype, tol, b, s, hq, hkv, dh, window, cap):
    q, k, v, pos = _mk(b, s, s, hq, hkv, dh, dtype)
    out = attention(q, k, v, q_positions=pos, window=window, softcap_val=cap, block_kv=64)
    ref = attention_reference(q, k, v, q_positions=pos, window=window, softcap_val=cap)
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


@pytest.mark.parametrize("s,t,causal", [
    (257, 257, True),    # padded last block, causal self-attention
    (96, 200, False),    # cross-attention: queries over a longer encoder
])
def test_blockwise_gradient_matches_reference(s, t, causal):
    """The blockwise path's gradients equal the naive oracle's, and its
    backward never differentiates the running max: that derivative divides
    by the count of scores equal to the max, which is zero — NaN gradients —
    whenever the backward pass recomputes bf16 scores with other rounding
    (as XLA does on TPU)."""
    q, k, v, pos = _mk(1, s, t, 4, 2, 32, jnp.float32)

    def loss(fn, **kw):
        return lambda q, k, v: jnp.sum(
            jnp.sin(fn(q, k, v, q_positions=pos, causal=causal, **kw)))

    blockwise = loss(attention, block_kv=64)
    got = jax.grad(blockwise, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(attention_reference), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert jnp.max(jnp.abs(g - w)) < 1e-4
    # the max's derivative compares every score with it: a score-shaped eq
    grad_jaxpr = str(jax.make_jaxpr(jax.grad(blockwise))(q, k, v))
    assert not re.search(r"bool\[\d+,\d+,\d+,\d+\] = eq ", grad_jaxpr)
