"""Mamba (S6) selective-scan as a Pallas TPU kernel.

The diagonal recurrence h_t = e^{Δ_t·A} ⊙ h_{t−1} + (Δ_t u_t) B_t is
sequential in time but embarrassingly parallel over the (d_inner × state)
plane — on TPU the natural mapping is: channel blocks on the parallel grid
axes, time as an in-kernel ``fori_loop`` over a VMEM-resident (d_block, N)
state (GPU implementations instead use warp-level prefix scans; the VREG/VMEM
hierarchy prefers the wide-vector sequential form — DESIGN.md §5).

Inputs are the *factored* tensors (Δ, A, B, C, u) — the (B, S, d, N) outer
products are never materialized in HBM (the XLA associative-scan path
materializes both ``da`` and ``dbu``; this kernel is the memory-roofline fix
for mamba layers).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(dt_ref, u_ref, b_ref, c_ref, a_ref, y_ref, h_scr, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[...].astype(jnp.float32)  # (N, dib)

    # the state is held (N, dib): channels on lanes, state on sublanes, so a
    # time step reads one (1, dib) row of dt/u and one (N, 1) column of B/C
    # straight from the refs (Mosaic cannot index a loaded value by the
    # loop counter)
    def step(t, _):
        dt = dt_ref[pl.ds(t, 1), :].astype(jnp.float32)  # (1, dib)
        u = u_ref[pl.ds(t, 1), :].astype(jnp.float32)
        b_t = b_ref[t].astype(jnp.float32)  # (N, 1)
        c_t = c_ref[t].astype(jnp.float32)
        h = jnp.exp(dt * a) * h_scr[...] + b_t * (dt * u)
        h_scr[...] = h
        y_ref[pl.ds(t, 1), :] = jnp.sum(h * c_t, axis=0, keepdims=True).astype(y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)


def ssm_scan(dt, u, b_t, c_t, a, *, chunk: int = 128, d_block: int = 256,
             interpret: bool = False):
    """dt/u: (B, S, di); b_t/c_t: (B, S, N); a: (di, N). Returns y (B, S, di)
    (the h·C contraction; caller adds the D-skip and gating)."""
    b, s, di = dt.shape
    n = a.shape[1]
    dtype = dt.dtype
    # the kernel reads one time step's row at a dynamic sublane offset, which
    # Mosaic can load only from unpacked (32-bit) tiles
    dt, u, b_t, c_t = (x.astype(jnp.float32) for x in (dt, u, b_t, c_t))
    d_block = min(d_block, di)
    assert di % d_block == 0, (di, d_block)
    pad = (-s) % chunk
    if pad:
        dt, u = (jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (dt, u))
        b_t, c_t = (jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (b_t, c_t))
    sp = s + pad
    n_chunks = sp // chunk

    row = pl.BlockSpec((None, chunk, d_block), lambda b_, dbi, ci: (b_, ci, dbi))
    col = pl.BlockSpec((None, chunk, n, 1), lambda b_, dbi, ci: (b_, ci, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(b, di // d_block, n_chunks),
        in_specs=[row, row, col, col,
                  pl.BlockSpec((n, d_block), lambda b_, dbi, ci: (0, dbi))],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((b, sp, di), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, d_block), jnp.float32)],
        interpret=interpret,
    )(dt, u, b_t[..., None], c_t[..., None], a.T)
    return out[:, :s].astype(dtype)
