"""Parameter-spec machinery and basic layers (norm, rope, MLP, embedding).

Parameters are declared as ``PSpec`` leaves (shape + logical axes + init) so the
same declaration yields (a) ``jax.ShapeDtypeStruct`` trees for AOT dry-runs with
no allocation, (b) real initialized arrays for smoke tests / examples, and
(c) ``PartitionSpec`` trees via logical→mesh axis rules.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class PSpec:
    """Declarative parameter leaf."""

    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]  # logical axis names (len == len(shape))
    init: str = "normal"  # normal | zeros | ones | small_normal
    scale: float = 0.0  # 0 -> 1/sqrt(fan_in) for normal

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def abstract_params(tree, dtype) -> Any:
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
        tree,
        is_leaf=lambda x: isinstance(x, PSpec),
    )


def init_params(tree, rng, dtype) -> Any:
    leaves, treedef = jax.tree.flatten(tree, is_leaf=lambda x: isinstance(x, PSpec))
    rngs = jax.random.split(rng, len(leaves))
    out = []
    for spec, r in zip(leaves, rngs):
        if spec.init == "zeros":
            out.append(jnp.zeros(spec.shape, dtype))
        elif spec.init == "ones":
            out.append(jnp.ones(spec.shape, dtype))
        else:
            fan_in = spec.shape[0] if spec.shape else 1
            scale = spec.scale or (1.0 / max(fan_in, 1)) ** 0.5
            if spec.init == "small_normal":
                scale = 0.02
            out.append(scale * jax.random.normal(r, spec.shape, dtype))
    return jax.tree.unflatten(treedef, out)


def partition_specs(tree, rules: Dict[str, Any]) -> Any:
    """Map logical axes to mesh axes. ``rules[name]`` is a mesh axis (str),
    tuple of mesh axes, or None. Axes whose dimension is not divisible by the
    mapped mesh-axis size (``rules["_sizes"]``) fall back to replication."""
    sizes = rules.get("_sizes", {})

    def axis_product(r) -> int:
        names = (r,) if isinstance(r, str) else tuple(r)
        return math.prod(sizes.get(n, 1) for n in names)

    def one(spec: PSpec) -> P:
        out = []
        for dim, a in zip(spec.shape, spec.axes):
            r = rules.get(a) if a is not None else None
            if r is not None and dim % axis_product(r) != 0:
                r = None
            out.append(r)
        return P(*out)

    return jax.tree.map(one, tree, is_leaf=lambda x: isinstance(x, PSpec))


def logical_sharding_constraint(x, axes: Tuple[Optional[str], ...], rules):
    spec = P(*[rules.get(a) if a is not None else None for a in axes])
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------


def rms_norm(x, weight, eps: float):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dt)


def rms_norm_specs(d: int) -> PSpec:
    # stored as a zero-centered scale (gemma convention); init zeros == identity
    return PSpec((d,), ("embed",), init="zeros")


def rotary_embedding(x, positions, theta: float):
    """x: (..., S, H, Dh); positions: broadcastable to (..., S)."""
    dh = x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freq  # (..., S, half)
    angles = angles[..., None, :]  # (..., S, 1, half) broadcast over heads
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def softcap(x, cap: float):
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


def gated_mlp_specs(d: int, ff: int) -> Dict[str, PSpec]:
    return {
        "wi_gate": PSpec((d, ff), ("embed", "ff")),
        "wi_up": PSpec((d, ff), ("embed", "ff")),
        "wo": PSpec((ff, d), ("ff", "embed")),
    }


def gated_mlp(params, x, compute_dtype):
    """SwiGLU MLP. x: (B, S, D)."""
    g = jnp.einsum("bsd,df->bsf", x, params["wi_gate"].astype(compute_dtype))
    u = jnp.einsum("bsd,df->bsf", x, params["wi_up"].astype(compute_dtype))
    h = jax.nn.silu(g) * u
    return jnp.einsum("bsf,fd->bsd", h, params["wo"].astype(compute_dtype))


def embed_specs(vocab: int, d: int) -> PSpec:
    return PSpec((vocab, d), ("vocab", "embed"), init="small_normal")


def embed_lookup(table, tokens, compute_dtype):
    return table.astype(compute_dtype)[tokens]


def unembed(x, table, compute_dtype, cap: float = 0.0):
    logits = jnp.einsum("bsd,vd->bsv", x, table.astype(compute_dtype))
    logits = softcap(logits.astype(jnp.float32), cap)
    return logits


def cross_entropy(logits, labels, vocab_size: int):
    """logits: (B, S, Vpad) any float dtype (converted to f32 inside the
    reductions, which XLA fuses — no materialized f32 copy); labels int32
    (B, S). Ignores padded vocab tail and label = -1 positions."""
    logits = logits.astype(jnp.float32)
    vpad = logits.shape[-1]
    if vpad > vocab_size:
        # where + iota (not scatter) so the masking partitions cleanly when the
        # vocab axis is sharded over the model axis.
        vocab_ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
        logits = jnp.where(vocab_ids < vocab_size, logits, -1e9)
    lse = jax.nn.logsumexp(logits, axis=-1)
    # select-and-reduce rather than take_along_axis: the backward pass is then
    # an elementwise select instead of a scatter, which both partitions better
    # under GSPMD and avoids XLA's scatter-partitioner edge cases inside
    # partial-manual shard_map regions.
    vocab_ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    onehot = vocab_ids == labels[..., None]
    ll = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    mask = (labels >= 0).astype(logits.dtype)
    return jnp.sum((lse - ll) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
