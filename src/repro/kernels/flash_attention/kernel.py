"""Flash attention as a Pallas TPU kernel (online softmax, VMEM-resident
blocks, MXU-aligned tiles).

TPU adaptation of the FlashAttention idea (the paper's GPU formulation works
around SRAM/shared-memory; here the tiling is driven by VMEM capacity and the
128×128 MXU):

  - grid = (batch, q_heads, q_blocks, kv_blocks); the kv axis is the
    innermost, sequential ("arbitrary") dimension — running-max/denominator/
    accumulator live in VMEM scratch across kv iterations, so scores never
    round-trip to HBM (the XLA fallback path materializes every (S × block)
    score tile — that difference IS the memory-roofline gap the dry-run
    shows).
  - ``block_q × block_kv`` tiles are the tunable knobs ``attn_block_q/kv``
    exposed to the paper's tuner; both must be multiples of 128 to keep the
    MXU systolic array full.
  - GQA: the kv BlockSpec maps query-head h → kv-head h·Hkv//Hq, so K/V
    blocks are fetched once per query head from a head-major (B,Hkv,T,Dh)
    copy — no repeated/materialized K/V.
  - causal + sliding-window masking is applied with block-level early-exit:
    fully-masked (q-block, kv-block) pairs are skipped before the matmul
    (``@pl.when``), which is where the causal 2× win comes from.

Supports: causal/full, sliding window, logit softcap, GQA, optional
``kv_length`` (valid-prefix) masking. f32 accumulation throughout.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    # refs
    q_ref,  # (block_q, dh)
    k_ref,  # (block_kv, dh)
    v_ref,  # (block_kv, dh)
    o_ref,  # (block_q, dh)
    m_scr,  # (block_q,) f32 running max
    l_scr,  # (block_q,) f32 running denominator
    acc_scr,  # (block_q, dh) f32 accumulator
    *,
    causal: bool,
    window: int,
    softcap: float,
    scale: float,
    block_q: int,
    block_kv: int,
    n_kv: int,
    t_valid: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_kv

    # block-level early exit: skip fully-masked tiles before touching the MXU
    live = jnp.bool_(True)
    if causal:
        live &= k_start <= q_start + block_q - 1  # block fully in the future
    if window > 0:
        # block fully older than the window of the youngest query in the tile
        live &= k_start + block_kv - 1 >= q_start - window + 1

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale  # (bq, dh)
        k = k_ref[...].astype(jnp.float32)  # (bkv, dh)
        v = v_ref[...].astype(jnp.float32)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bkv)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = k_pos < t_valid
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= q_pos - k_pos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])  # (bq, bkv)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[...] = acc_scr[...] * corr[:, None] + pv
        m_scr[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jnp.ndarray,  # (B, S, Hq, Dh)
    k: jnp.ndarray,  # (B, T, Hkv, Dh)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    kv_length: Optional[int] = None,
    block_q: int = 128,
    block_kv: int = 128,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas flash attention. ``scale`` defaults to dh^-0.5 (pass 1.0 for
    pre-scaled q). Static window / kv_length (the model routes traced windows
    to the XLA path)."""
    b, s, hq, dh = q.shape
    t, hkv = k.shape[1], k.shape[2]
    scale = dh**-0.5 if scale is None else scale

    # clamp to the 128-padded lengths, not the raw ones: min(block, s) on a
    # ragged s (e.g. 200) would silently de-align the MXU tile the ops layer
    # just snapped; the pad below absorbs the overhang instead
    block_q = max(1, min(block_q, -(-s // 128) * 128))
    block_kv = max(1, min(block_kv, -(-t // 128) * 128))
    pad_q = (-s) % block_q
    pad_kv = (-t) % block_kv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    sp, tp = s + pad_q, t + pad_kv
    n_q, n_kv = sp // block_q, tp // block_kv
    t_valid = t if kv_length is None else int(kv_length)

    kernel = functools.partial(
        _kernel,
        causal=causal,
        window=int(window),
        softcap=float(softcap),
        scale=scale,
        block_q=block_q,
        block_kv=block_kv,
        n_kv=n_kv,
        t_valid=t_valid,
    )

    # heads ahead of the sequence: a (block, dh) tile is then the trailing
    # two dims of the array, which is the only tiling Mosaic accepts when a
    # head axis sits between them (dh is rarely a multiple of 128)
    q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = pl.pallas_call(
        kernel,
        grid=(b, hq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((None, None, block_q, dh), lambda b_, h, qi, ki: (b_, h, qi, 0)),
            pl.BlockSpec((None, None, block_kv, dh), lambda b_, h, qi, ki: (b_, h * hkv // hq, ki, 0)),
            pl.BlockSpec((None, None, block_kv, dh), lambda b_, h, qi, ki: (b_, h * hkv // hq, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, dh), lambda b_, h, qi, ki: (b_, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sp, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out.transpose(0, 2, 1, 3)[:, :s]
