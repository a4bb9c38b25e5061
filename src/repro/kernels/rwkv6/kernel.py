"""RWKV-6 (Finch) chunked WKV recurrence as a Pallas TPU kernel.

The data-dependent-decay linear attention is computed chunk-parallel: within
a chunk of C tokens the decay products are factored into the queries/keys so
the intra-chunk part is two C×C / C×K matmuls (MXU work); across chunks a
(K, V) state matrix is carried in VMEM scratch — the time axis is the
sequential grid dimension, exactly mirroring the ``lax.scan`` in
``repro.models.rwkv6.time_mix`` (the pure-jnp oracle).

Grid: (B, H, n_chunks) with the chunk axis innermost/sequential. Blocks:
r/k/v/logw tiles of (C, hd) from a head-major (B, H, S, hd) copy of the
inputs, ``u`` (per-head bonus) as a (1, hd) tile. All accumulation in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# every matmul at full f32: the decay factors reach e^±88 inside a chunk, and
# one bf16 pass on the MXU (the TPU default for f32) loses the cancellation
# the factorization relies on
_HIGHEST = jax.lax.Precision.HIGHEST

def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, state_scr, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    r = r_ref[...].astype(jnp.float32)  # (C, K)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)  # (C, V)
    lw = lw_ref[...].astype(jnp.float32)  # (C, K) log-decay (<0)
    u = u_ref[...].astype(jnp.float32)  # (1, K)

    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    tj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive cumsum over time as a lower-triangular matmul (Mosaic has no
    # cumsum)
    lcum = jax.lax.dot_general(
        (tj <= ti).astype(jnp.float32), lw, (((1,), (0,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32,
    )
    ltot = lcum[-1:, :]  # (1, K)
    q_f = r * jnp.exp(lcum - lw)
    k_f = k * jnp.exp(-lcum)

    scores = jax.lax.dot_general(
        q_f, k_f, (((1,), (1,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (C, C)
    scores = jnp.where(tj < ti, scores, 0.0)  # strictly past tokens

    diag = jnp.sum(r * u * k, axis=1)  # (C,) current-token bonus
    o = jax.lax.dot_general(
        scores, v, (((1,), (0,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32,
    )
    o += diag[:, None] * v
    o += jax.lax.dot_general(
        q_f, state_scr[...], (((1,), (0,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32,
    )

    k_s = k * jnp.exp(ltot - lcum)  # decays from token to end of chunk
    state_scr[...] = jnp.exp(ltot).T * state_scr[...] + jax.lax.dot_general(
        k_s, v, (((0,), (0,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = o.astype(o_ref.dtype)


def wkv6_chunked(r, k, v, logw, u, *, chunk: int = 64, interpret: bool = False):
    """r/k/v/logw: (B, S, H, hd); u: (H, hd). Returns (B, S, H, hd) (the WKV
    mix output, before group-norm/gating)."""
    b, s, h, hd = r.shape
    pad = (-s) % chunk
    if pad:
        r, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (r, k, v))
        logw = jnp.pad(logw, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = s + pad
    n_chunks = sp // chunk

    # heads ahead of the sequence, so each (chunk, hd) tile is the trailing
    # two dims of its array (Mosaic's tiling rule); u likewise as (H, 1, hd)
    r, k, v, logw = (x.transpose(0, 2, 1, 3) for x in (r, k, v, logw))
    tile = pl.BlockSpec((None, None, chunk, hd), lambda b_, h_, ci: (b_, h_, ci, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(b, h, n_chunks),
        in_specs=[tile, tile, tile, tile,
                  pl.BlockSpec((None, 1, hd), lambda b_, h_, ci: (h_, 0, 0))],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((b, h, sp, hd), r.dtype),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(r, k, v, logw, u.reshape(h, 1, hd))
    return out.transpose(0, 2, 1, 3)[:, :s]
