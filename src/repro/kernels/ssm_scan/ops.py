"""Jitted wrapper for the selective-scan kernel.

``chunk`` is clamped to the sequence length and ``d_block`` halved until it
divides the channel dim (both idempotent, so any tuner proposal is legal);
when the caller passes nothing the study-tuned table for this
(dtype, shape-class) fills them."""
from __future__ import annotations

from typing import Optional

from repro.kernels import dtype_token, ssm_shape_class, tuned_config
from repro.kernels.ssm_scan.kernel import ssm_scan

DEFAULT_CHUNK = 128
DEFAULT_D_BLOCK = 256


def snap_chunk(chunk: int, seq_len: int) -> int:
    """Clamp a chunk length to the sequence (idempotent)."""
    return max(1, min(int(chunk), int(seq_len)))


def snap_d_block(d_block: int, di: int) -> int:
    """Halve until it divides the channel dim (idempotent)."""
    d_block = max(1, int(d_block))
    while di % d_block:
        d_block //= 2
    return max(d_block, 1)


def vmem_footprint(chunk: int, d_block: int, n: int, dtype_bytes: int = 4) -> int:
    """Analytic per-core VMEM bytes for one (batch, d_block, chunk) grid
    step: the dt/u/out (chunk × d_block) tiles and the B/C tiles (chunk
    (n, 1) columns, each padded to n rounded up to 8 sublanes × 128 lanes),
    all f32 whatever the input dtype, the (n × d_block) A tile at the input
    dtype, the (n × d_block) f32 state scratch, and the f32 working tiles the
    in-kernel scan materializes. Monotone in both ``chunk`` and ``d_block``."""
    c, db, n = int(chunk), int(d_block), int(n)
    cols = 2 * c * (-(-n // 8) * 8) * 128
    tiles = (3 * c * db + cols) * 4 + db * n * int(dtype_bytes)
    scratch = db * n * 4
    work = (c * db + c * n) * 4
    return tiles + scratch + work


def selective_scan(dt, u, b_t, c_t, a, *, chunk: Optional[int] = None,
                   d_block: Optional[int] = None, interpret: bool = False):
    if chunk is None or d_block is None:
        tuned = tuned_config(
            "ssm_scan", dtype_token(dt.dtype),
            ssm_shape_class(dt.shape, a.shape[-1]),
        ) or {}
        if chunk is None:
            chunk = int(tuned.get("chunk", DEFAULT_CHUNK))
        if d_block is None:
            d_block = int(tuned.get("d_block", DEFAULT_D_BLOCK))
    chunk = snap_chunk(chunk, dt.shape[1])
    d_block = snap_d_block(d_block, dt.shape[-1])
    return ssm_scan(dt, u, b_t, c_t, a, chunk=chunk, d_block=d_block,
                    interpret=interpret)
