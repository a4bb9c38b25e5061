"""GQA attention: flash-style chunked XLA path + Pallas kernel dispatch.

The XLA path is a blockwise online-softmax implementation written with
``lax.scan`` over KV blocks, so that (a) peak memory stays O(S·block_kv) rather
than O(S·T) — required for the 32k prefill dry-runs — and (b) the tunable
``attn_block_kv`` knob is meaningful on both paths. The Pallas path (TPU
target) lives in ``repro.kernels.flash_attention``.

GQA takes one of two forms, by branch. The single-shot branch (decode, and
contexts of at most ``block_kv``) groups the query heads over their KV head
(``(B, S, Hkv, G, Dh)`` against ``(B, T, Hkv, Dh)``), so each KV head is read
once per step and no repeated copy of K/V is made. The blockwise branch repeats
K/V to the full query-head count *inside each KV block*, so its activations
carry a flat head axis that is divisible by the model-parallel degree whenever
``num_heads`` is (the (Hkv, G) factored layout cannot be sharded 16-way when
both factors are < 16, e.g. qwen2's 8 × 8). With G == 1 (MHA) both branches
use K/V as they are. ``window`` may be a traced per-layer scalar (≤ 0 means
full context), which lets local/global alternating stacks (gemma2/gemma3)
share one scanned layer body.
"""
from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp

NEG_INF = -1e30

Window = Union[int, jnp.ndarray]


def _is_static_zero(window: Window) -> bool:
    return isinstance(window, (int, float)) and window == 0


def _softcap(s, cap: float):
    if not cap:
        return s
    return cap * jnp.tanh(s / cap)


def _mask(qpos, kpos, *, causal: bool, window: Window, kv_length):
    """qpos: (B,1,S,1); kpos: (1,1,1,T) -> bool (B,1,S,T)."""
    mask = jnp.ones(jnp.broadcast_shapes(qpos.shape, kpos.shape), bool)
    if causal:
        mask &= kpos <= qpos
    if not _is_static_zero(window):
        w = jnp.asarray(window)
        mask &= (qpos - kpos < w) | (w <= 0)
    if kv_length is not None:
        mask &= kpos < kv_length[:, None, None, None]
    return mask


def attention(
    q,
    k,
    v,
    *,
    q_positions,
    kv_length: Optional[jnp.ndarray] = None,
    causal: bool = True,
    window: Window = 0,
    softcap_val: float = 0.0,
    block_kv: int = 512,
    impl: str = "xla",
    interpret: bool = False,
    unroll: bool = False,
    new_kv=None,
):
    """Grouped-query attention.

    q: (B, S, Hq, Dh); k, v: (B, T, Hkv, Dh). ``q_positions``: (B, S) global
    positions of the queries (supports decode with cache offset).
    ``kv_length``: optional (B,) valid KV prefix length (decode caches).
    ``new_kv``: optional decode-step (k, v), each (B, 1, Hkv, Dh): the
    query's own K/V, attended beside ``k``/``v`` as one more position, so a
    step reads its cache as it came in and need not write into it first.
    ``window``: 0 = full; > 0 = sliding window; may be a traced scalar
    (then ≤ 0 means full). Returns (B, S, Hq, Dh).
    """
    b, s, hq, dh = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = dh**-0.5
    qs = q * scale
    if new_kv is not None and (s != 1 or impl != "xla"):
        raise ValueError("new_kv is for single-token decode on the XLA path")

    if impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops

        return fa_ops.flash_attention(
            qs,
            k,
            v,
            q_positions=q_positions,
            kv_length=kv_length,
            causal=causal,
            window=window,
            softcap_val=softcap_val,
            block_kv=block_kv,
            interpret=interpret,
        )

    def expand(x):  # (B, T', Hkv, Dh) -> (B, T', Hq, Dh)
        if g == 1:
            return x
        return jnp.repeat(x, g, axis=2)

    qpos = q_positions[:, None, :, None]  # (B,1,S,1)

    if s == 1 or t <= block_kv:
        # Decode / short context: single-shot masked attention (linear in T).
        # Each KV head serves its group of G query heads as it is: scores
        # (B,Hkv,G,S,T), the mask broadcast over G.
        qs = qs.reshape(b, s, hkv, g, dh)
        scores = jnp.einsum("bskgd,btkd->bkgst", qs, k)
        scores = _softcap(scores, softcap_val)
        kpos = jnp.arange(t)[None, None, None, :]
        m = _mask(qpos, kpos, causal=causal, window=window, kv_length=kv_length)
        scores = jnp.where(m[:, :, None], scores.astype(jnp.float32), NEG_INF)
        if new_kv is None:
            probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
            out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
            return out.reshape(b, s, hq, dh)
        # the current token's K/V, at the query's own position, beside the
        # cache's positions: one softmax over both
        k_new, v_new = new_kv  # (B, 1, Hkv, Dh)
        s_new = jnp.einsum("bskgd,bskd->bkgs", qs, k_new)[..., None]
        s_new = _softcap(s_new, softcap_val).astype(jnp.float32)
        top = jnp.maximum(jnp.max(scores, -1, keepdims=True), s_new)
        e, e_new = jnp.exp(scores - top), jnp.exp(s_new - top)
        denom = jnp.sum(e, -1, keepdims=True) + e_new
        probs, p_new = (e / denom).astype(q.dtype), (e_new / denom).astype(q.dtype)
        out = jnp.einsum("bkgst,btkd->bskgd", probs, v, preferred_element_type=jnp.float32)
        out = out + jnp.einsum("bkgst,bskd->bskgd", p_new, v_new, preferred_element_type=jnp.float32)
        return out.astype(q.dtype).reshape(b, s, hq, dh)

    # Blockwise online-softmax over KV blocks.
    n_blocks = -(-t // block_kv)
    pad = n_blocks * block_kv - t
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, n_blocks, block_kv, hkv, dh).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, n_blocks, block_kv, hkv, dh).transpose(1, 0, 2, 3, 4)

    def body(carry, blk):
        m_run, l_run, acc = carry
        kblk, vblk, idx = blk  # (B, block, Hkv, Dh)
        kf, vf = expand(kblk), expand(vblk)
        scores = jnp.einsum("bshd,bthd->bhst", qs, kf)  # (B,Hq,S,block)
        scores = _softcap(scores, softcap_val)
        kpos = idx * block_kv + jnp.arange(block_kv)[None, None, None, :]
        msk = (kpos < t) & _mask(
            qpos, kpos, causal=causal, window=window, kv_length=kv_length
        )
        scores = jnp.where(msk, scores.astype(jnp.float32), NEG_INF)
        # the running max only stabilizes the exponentials (out = acc / l does
        # not depend on it), so no gradient flows through it — as in
        # jax.nn.softmax. Differentiating the max instead divides by the count
        # of entries equal to it, which is 0 (NaN gradients) when the backward
        # pass recomputes bf16 scores with a different rounding, as on TPU.
        m_blk = jax.lax.stop_gradient(jnp.max(scores, axis=-1))
        m_new = jnp.maximum(m_run, m_blk)
        corr = jnp.exp(m_run - m_new)
        p = jnp.exp(scores - m_new[..., None])  # (B,Hq,S,block)
        l_new = l_run * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhst,bthd->bhsd", p.astype(q.dtype), vf)
        acc_new = acc * corr[..., None] + pv.astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hq, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hq, s), jnp.float32)
    acc0 = jnp.zeros((b, hq, s, dh), jnp.float32)
    (m_run, l_run, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (kb, vb, jnp.arange(n_blocks)), unroll=unroll
    )
    out = acc / jnp.maximum(l_run, 1e-30)[..., None]  # (B,Hq,S,Dh)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def attention_reference(q, k, v, *, q_positions, kv_length=None, causal=True,
                        window=0, softcap_val=0.0):
    """Naive O(S·T) oracle used by tests."""
    b, s, hq, dh = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", q * dh**-0.5, k)
    scores = _softcap(scores, softcap_val).astype(jnp.float32)
    kpos = jnp.arange(t)[None, None, None, :]
    qpos = q_positions[:, None, :, None]
    m = _mask(qpos, kpos, causal=causal, window=window, kv_length=kv_length)
    scores = jnp.where(m, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)
