"""Generic layer-stack machinery shared by all 10 assigned architectures.

A model is a stack of ``num_layers`` layers. Layers are described by
``LayerDesc`` (mixer kind + MoE flag + cross-attention flag). The stack is
executed as a ``lax.scan`` over *structural groups*: the shortest repeating
unit of structurally distinct layers (e.g. jamba's [attn, mamba×7] with MoE on
odd layers → period 8; llama4's dense/MoE alternation → period 2; plain dense
stacks → period 1). Within a group the (few) layers are unrolled; across
groups parameters/caches are stacked along a leading axis and scanned, keeping
the HLO size O(period) instead of O(num_layers).

Attention locality (gemma2/gemma3 local:global patterns) is NOT structural:
the sliding-window size is a per-layer *value* (a scanned int32 array, ≤ 0
meaning full attention), so local and global layers share one traced body.

Three modes:
  - ``train``   — full sequence, no caches.
  - ``prefill`` — full sequence, emits per-layer caches (KV / SSM / RWKV).
  - ``decode``  — single token, reads the caches and writes its new token
    rows (or replaced states) into them in place.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, RunConfig
from repro.models import mamba as mamba_mod
from repro.models import moe as moe_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models.attention import attention
from repro.models.layers import (
    PSpec,
    gated_mlp,
    gated_mlp_specs,
    rms_norm,
    rms_norm_specs,
    rotary_embedding,
)


# ---------------------------------------------------------------------------
# Layer descriptors / structural periods
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class LayerDesc:
    kind: str  # attn | mamba | rwkv
    is_moe: bool
    cross: bool = False  # decoder layer with cross-attention (enc-dec archs)


def layer_descs(arch: ArchConfig) -> Tuple[LayerDesc, ...]:
    cross = arch.encoder_layers > 0
    out = []
    for kind, is_moe in arch.layer_kinds():
        k = "attn" if kind in ("attn", "attn_local") else kind
        out.append(LayerDesc(k, is_moe, cross))
    return tuple(out)


def structural_period(arch: ArchConfig) -> int:
    """Shortest repeating unit of *structurally distinct* layers."""
    descs = layer_descs(arch)
    n = len(descs)
    for p in range(1, n + 1):
        if n % p == 0 and all(descs[i] == descs[i % p] for i in range(n)):
            return p
    return n


def num_groups(arch: ArchConfig) -> int:
    return arch.num_layers // structural_period(arch)


def windows_array(arch: ArchConfig) -> jnp.ndarray:
    """(num_layers,) per-layer sliding window; 0 = full attention."""
    wins = []
    for i in range(arch.num_layers):
        kind = arch.block_pattern[i % len(arch.block_pattern)]
        wins.append(arch.sliding_window if kind == "attn_local" else 0)
    return jnp.asarray(wins, jnp.int32)


def has_dynamic_window(arch: ArchConfig) -> bool:
    return any(k == "attn_local" for k in arch.block_pattern)


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------


def attn_specs(arch: ArchConfig, cross: bool = False) -> Dict[str, PSpec]:
    d = arch.d_model
    dh = arch.resolved_head_dim
    hq, hkv = arch.num_heads, arch.num_kv_heads
    prefix = "c" if cross else ""
    specs = {
        prefix + "wq": PSpec((d, hq * dh), ("embed", "heads_out")),
        prefix + "wk": PSpec((d, hkv * dh), ("embed", "kv_out")),
        prefix + "wv": PSpec((d, hkv * dh), ("embed", "kv_out")),
        prefix + "wo": PSpec((hq * dh, d), ("heads_out", "embed")),
    }
    if arch.qkv_bias and not cross:
        specs[prefix + "bq"] = PSpec((hq * dh,), ("heads_out",), init="zeros")
        specs[prefix + "bk"] = PSpec((hkv * dh,), ("kv_out",), init="zeros")
        specs[prefix + "bv"] = PSpec((hkv * dh,), ("kv_out",), init="zeros")
    return specs


def layer_specs(arch: ArchConfig, desc: LayerDesc) -> Dict[str, Any]:
    d = arch.d_model
    if desc.kind == "rwkv":
        specs = rwkv_mod.rwkv_specs(arch)
        specs["ln1"] = rms_norm_specs(d)
        specs["ln2"] = rms_norm_specs(d)
        return specs
    specs: Dict[str, Any] = {"ln1": rms_norm_specs(d), "ln2": rms_norm_specs(d)}
    if desc.kind == "attn":
        specs["attn"] = attn_specs(arch)
        if desc.cross:
            specs["xattn"] = attn_specs(arch, cross=True)
            specs["lnx"] = rms_norm_specs(d)
    elif desc.kind == "mamba":
        specs["mamba"] = mamba_mod.mamba_specs(arch)
    else:
        raise ValueError(desc.kind)
    if desc.is_moe:
        specs["moe"] = moe_mod.moe_specs(arch)
    else:
        specs["mlp"] = gated_mlp_specs(d, arch.d_ff)
    return specs


def _stack_tree(tree, n: int):
    return jax.tree.map(
        lambda s: PSpec((n,) + s.shape, (None,) + s.axes, s.init, s.scale),
        tree,
        is_leaf=lambda x: isinstance(x, PSpec),
    )


def stack_specs(arch: ArchConfig) -> Dict[str, Any]:
    """Stacked decoder stack params: {"l{j}": specs} × num_groups."""
    period = structural_period(arch)
    assert arch.num_layers % period == 0, (arch.name, arch.num_layers, period)
    descs = layer_descs(arch)[:period]
    group = {f"l{j}": layer_specs(arch, descs[j]) for j in range(period)}
    return _stack_tree(group, num_groups(arch))


def encoder_stack_specs(arch: ArchConfig) -> Dict[str, Any]:
    """Whisper-style encoder: plain non-causal attention layers."""
    desc = LayerDesc("attn", False, False)
    group = {"l0": layer_specs(arch, desc)}
    return _stack_tree(group, arch.encoder_layers)


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------


def layer_cache_specs(
    arch: ArchConfig, desc: LayerDesc, batch: int, capacity: int, run: RunConfig
) -> Dict[str, PSpec]:
    """Cache leaves for one layer (un-stacked)."""
    dh = arch.resolved_head_dim
    hkv = arch.num_kv_heads
    if desc.kind == "attn":
        cache = {
            "k": PSpec((batch, capacity, hkv, dh), ("act_batch", "kv_seq", "kv_heads", None), init="zeros"),
            "v": PSpec((batch, capacity, hkv, dh), ("act_batch", "kv_seq", "kv_heads", None), init="zeros"),
        }
        if run.kv_cache_dtype == "int8":
            cache["ks"] = PSpec((batch, capacity, hkv), ("act_batch", "kv_seq", "kv_heads"), init="ones")
            cache["vs"] = PSpec((batch, capacity, hkv), ("act_batch", "kv_seq", "kv_heads"), init="ones")
        if desc.cross:
            f = arch.frontend_seq
            cache["ck"] = PSpec((batch, f, hkv, dh), ("act_batch", None, "kv_heads", None), init="zeros")
            cache["cv"] = PSpec((batch, f, hkv, dh), ("act_batch", None, "kv_heads", None), init="zeros")
        return cache
    if desc.kind == "mamba":
        di = arch.ssm_expand * arch.d_model
        return {
            "conv": PSpec((batch, arch.ssm_conv_width - 1, di), ("act_batch", None, "inner"), init="zeros"),
            "ssm": PSpec((batch, di, arch.ssm_state_dim), ("act_batch", "inner", None), init="zeros"),
        }
    if desc.kind == "rwkv":
        d = arch.d_model
        hd = arch.rwkv_head_dim
        return {
            "wkv": PSpec((batch, d // hd, hd, hd), ("act_batch", "heads", None, None), init="zeros"),
            "shift_t": PSpec((batch, d), ("act_batch", "act_embed"), init="zeros"),
            "shift_c": PSpec((batch, d), ("act_batch", "act_embed"), init="zeros"),
        }
    raise ValueError(desc.kind)


def cache_specs(arch: ArchConfig, batch: int, capacity: int, run: RunConfig) -> Dict[str, Any]:
    period = structural_period(arch)
    descs = layer_descs(arch)[:period]
    group = {
        f"l{j}": layer_cache_specs(arch, descs[j], batch, capacity, run)
        for j in range(period)
    }
    return _stack_tree(group, num_groups(arch))


def cache_dtypes(arch: ArchConfig, run: RunConfig, tree) -> Any:
    """Per-leaf dtype for a cache tree: KV in kv_cache_dtype, scales/SSM f32,
    shift states in compute dtype."""

    def leaf_dtype(path_leaf):
        path, _ = path_leaf
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in ("k", "v", "ck", "cv"):
            return jnp.int8 if run.kv_cache_dtype == "int8" else jnp.bfloat16
        if name in ("ks", "vs", "ssm", "wkv"):
            return jnp.float32
        return jnp.dtype(run.compute_dtype)

    paths = jax.tree_util.tree_flatten_with_path(tree, is_leaf=lambda x: isinstance(x, PSpec))[0]
    dtypes = [leaf_dtype(pl) for pl in paths]
    treedef = jax.tree.structure(tree, is_leaf=lambda x: isinstance(x, PSpec))
    return jax.tree.unflatten(treedef, dtypes)


# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------


@dataclass
class Ctx:
    """Per-call context threaded through the stack."""

    arch: ArchConfig
    run: RunConfig
    mode: str  # train | prefill | decode
    positions: jnp.ndarray  # (B, S) global positions of the current tokens
    shard: Callable[[jnp.ndarray, Tuple[Optional[str], ...]], jnp.ndarray]
    cache_len: Optional[jnp.ndarray] = None  # scalar int32; valid prefix length
    enc_out: Optional[jnp.ndarray] = None  # (B, F, D) encoder output
    interpret: bool = False

    @property
    def compute_dtype(self):
        return jnp.dtype(self.run.compute_dtype)


def _quantize_kv(x):
    """(B,S,H,Dh) -> int8 values + (B,S,H) f32 scales."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.round(x.astype(jnp.float32) / scale[..., None]).astype(jnp.int8)
    return q, scale


def _dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


# cache leaves a decode step extends by one token row; it replaces the
# others whole (mamba, rwkv states) or only reads them (cross K/V)
_ROW_LEAVES = ("k", "v", "ks", "vs")


def _write_decode_cache(caches, written, pos):
    """The cache a decode step returns: the stacked cache tree ``caches``
    with what its layers ``written`` (stacked alike): the token rows of
    ``_ROW_LEAVES`` at sequence position ``pos``, the states a layer replaced
    as they are, and the leaves no layer wrote as they came in. The rows of
    every layer go in by one in-place update of each (donated) stack (named
    scope ``kv_cache``)."""

    def leaf(name, stack, new):
        if new is None:
            return stack
        if name not in _ROW_LEAVES:
            return new.astype(stack.dtype)
        start = (0, 0, pos) + (0,) * (stack.ndim - 3)  # (group, B, T, ...)
        return jax.lax.dynamic_update_slice(stack, new.astype(stack.dtype), start)

    with jax.named_scope("kv_cache"):
        return {
            lname: {name: leaf(name, stack, (written.get(lname) or {}).get(name))
                    for name, stack in leaves.items()}
            for lname, leaves in caches.items()
        }


def _group_cache(caches, gi):
    """Group ``gi``'s layer caches, read out of the stacks (named scope
    ``kv_cache``); None without caches."""
    if caches is None:
        return None
    with jax.named_scope("kv_cache"):
        return jax.tree.map(lambda c: jax.lax.dynamic_index_in_dim(c, gi, keepdims=False), caches)


def _attn_sublayer(p, h, ctx: Ctx, *, window, cache, prefix="", cross=False,
                   causal=True):
    """h: normed input (B,S,D). Returns (out (B,S,D), new_cache): the cache
    entries the sublayer produced (prefill: its whole K/V; decode: the new
    token's rows), None where it produced none.

    Named scopes (device time in a profiler trace): ``attn`` or ``xattn``
    around the whole sublayer; inside, ``qkv`` (projections, RoPE),
    ``kv_cache`` (cache reads and writes, int8 quantize/dequantize), ``sdpa``
    (the ``attention`` call) and ``out`` (output projection)."""
    with jax.named_scope("xattn" if cross else "attn"):
        return _attn_scoped(p, h, ctx, window=window, cache=cache, prefix=prefix,
                            cross=cross, causal=causal)


def _attn_scoped(p, h, ctx: Ctx, *, window, cache, prefix, cross, causal):
    arch, run = ctx.arch, ctx.run
    b, s, d = h.shape
    dh = arch.resolved_head_dim
    hq, hkv = arch.num_heads, arch.num_kv_heads
    cd = ctx.compute_dtype

    def proj(name, x_in, n_h):
        w = p[prefix + name].astype(cd)
        y = jnp.einsum("bsd,de->bse", x_in, w)
        bias = p.get(prefix + "b" + name[-1])
        if bias is not None:
            y = y + bias.astype(cd)
        if ctx.mode == "decode":
            # Decode's dot reads its weight in place only if what follows
            # cannot choose its output layout: RoPE's half-split of each
            # head otherwise makes the TPU compiler slice the layer's weight
            # out of the stack and copy it transposed, every step (wq: 72 MiB
            # a layer at d 6144).
            y = jax.lax.optimization_barrier(y)
        return y.reshape(b, -1, n_h, dh)

    def out_proj(o):
        with jax.named_scope("out"):
            return jnp.einsum("bse,ed->bsd", o.reshape(b, s, hq * dh),
                              p[prefix + "wo"].astype(cd))

    with jax.named_scope("qkv"):
        q = proj("wq", h, hq)
        q = ctx.shard(q, ("act_batch", "act_seq", "act_heads", None))
    new_cache = None

    if cross:
        # Cross-attention over the (fixed) encoder sequence: K/V computed from
        # the encoder output at train/prefill time and cached for decode.
        if ctx.mode == "decode":
            with jax.named_scope("kv_cache"):
                k = cache["ck"].astype(cd)
                v = cache["cv"].astype(cd)
        else:
            with jax.named_scope("qkv"):
                enc = ctx.enc_out.astype(cd)
                k = proj("wk", enc, hkv)
                v = proj("wv", enc, hkv)
            if ctx.mode == "prefill":
                with jax.named_scope("kv_cache"):
                    new_cache = {"ck": k.astype(jnp.bfloat16), "cv": v.astype(jnp.bfloat16)}
        with jax.named_scope("sdpa"):
            out = attention(
                q, k, v, q_positions=ctx.positions, kv_length=None, causal=False,
                window=0, softcap_val=0.0, block_kv=run.attn_block_kv, impl="xla",
                interpret=ctx.interpret,
            )
            out = ctx.shard(out, ("act_batch", "act_seq", "act_heads", None))
        return out_proj(out), new_cache

    with jax.named_scope("qkv"):
        k = proj("wk", h, hkv)
        v = proj("wv", h, hkv)
        q = rotary_embedding(q, ctx.positions, arch.rope_theta)
        k = rotary_embedding(k, ctx.positions, arch.rope_theta)
        k = ctx.shard(k, ("act_batch", "act_seq", "kv_heads", None))
        v = ctx.shard(v, ("act_batch", "act_seq", "kv_heads", None))

    k_scale = v_scale = None
    kv_len = new_kv = None
    with jax.named_scope("kv_cache"):
        if ctx.mode == "decode":
            # The layer's cache holds the positions before cache_len and
            # attention takes the new token's K/V beside it. Only these rows
            # leave the layer: ``_write_decode_cache`` puts them in the stacks.
            if run.kv_cache_dtype == "int8":
                kq, ks = _quantize_kv(k)
                vq, vs = _quantize_kv(v)
                new_cache = {"k": kq, "v": vq, "ks": ks, "vs": vs}
                k_scale, v_scale = cache["ks"], cache["vs"]
                new_kv = (_dequantize_kv(kq, ks, cd), _dequantize_kv(vq, vs, cd))
            else:
                new_cache = {"k": k.astype(cache["k"].dtype), "v": v.astype(cache["v"].dtype)}
                new_kv = (new_cache["k"].astype(cd), new_cache["v"].astype(cd))
            k_use, v_use = cache["k"], cache["v"]
            kv_len = jnp.full((b,), ctx.cache_len, jnp.int32)
        else:
            if ctx.mode == "prefill":
                if run.kv_cache_dtype == "int8":
                    kq, ks = _quantize_kv(k)
                    vq, vs = _quantize_kv(v)
                    new_cache = {"k": kq, "v": vq, "ks": ks, "vs": vs}
                else:
                    new_cache = {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}
            k_use, v_use = k, v

        if k_scale is not None:
            k_use = _dequantize_kv(k_use, k_scale, cd)
            v_use = _dequantize_kv(v_use, v_scale, cd)
        elif k_use.dtype != cd:
            k_use = k_use.astype(cd)
            v_use = v_use.astype(cd)

    with jax.named_scope("sdpa"):
        out = attention(
            q, k_use, v_use, q_positions=ctx.positions, kv_length=kv_len, new_kv=new_kv,
            causal=causal, window=window, softcap_val=arch.attn_logit_softcap,
            block_kv=run.attn_block_kv,
            impl=run.attention_impl if ctx.mode != "decode" else "xla",
            interpret=ctx.interpret, unroll=not run.scan_layers,
        )
        out = ctx.shard(out, ("act_batch", "act_seq", "act_heads", None))
    return out_proj(out), new_cache


def _ffn_sublayer(p, h, desc: LayerDesc, ctx: Ctx):
    """Returns (out, aux_loss). Named scope ``moe`` or ``mlp``."""
    if desc.is_moe:
        with jax.named_scope("moe"):
            out, aux = moe_mod.moe_apply(
                p["moe"], h, ctx.arch, ctx.compute_dtype, shard=ctx.shard
            )
        return out, aux
    with jax.named_scope("mlp"):
        return gated_mlp(p["mlp"], h, ctx.compute_dtype), 0.0


def apply_layer(p, x, desc: LayerDesc, ctx: Ctx, *, window, cache):
    """Pre-norm residual layer. Returns (x, aux_loss, new_cache); in decode,
    new_cache holds what the layer wrote (K/V rows, or its replaced states)."""
    arch = ctx.arch
    eps = arch.norm_eps
    aux = 0.0
    if desc.kind == "rwkv":
        if cache is None:
            b = x.shape[0]
            d = arch.d_model
            hd = arch.rwkv_head_dim
            cache = {
                "wkv": jnp.zeros((b, d // hd, hd, hd), jnp.float32),
                "shift_t": jnp.zeros((b, d), x.dtype),
                "shift_c": jnp.zeros((b, d), x.dtype),
            }
        with jax.named_scope("rwkv"):
            h = rms_norm(x, p["ln1"], eps)
            out, new_shift_t, new_wkv = rwkv_mod.time_mix(
                p["tmix"], h, cache["shift_t"].astype(x.dtype), cache["wkv"], arch,
                chunk=min(ctx.run.attn_block_kv, max(x.shape[1], 16)),
                unroll=not ctx.run.scan_layers,
            )
            x = x + out
            h2 = rms_norm(x, p["ln2"], eps)
            out2, new_shift_c = rwkv_mod.channel_mix(p["cmix"], h2, cache["shift_c"].astype(x.dtype))
            x = x + out2
            new_cache = {"wkv": new_wkv, "shift_t": new_shift_t.astype(cache["shift_t"].dtype),
                         "shift_c": new_shift_c.astype(cache["shift_c"].dtype)}
        return x, aux, (new_cache if ctx.mode != "train" else None)

    if desc.kind == "mamba":
        with jax.named_scope("mamba"):
            h = rms_norm(x, p["ln1"], eps)
            if ctx.mode == "decode":
                out, new_cache = mamba_mod.mamba_decode_step(p["mamba"], h, cache, arch)
            else:
                out, new_cache = mamba_mod.mamba_forward(
                    p["mamba"], h, arch, return_cache=(ctx.mode == "prefill")
                )
            x = x + out
    else:
        h = rms_norm(x, p["ln1"], eps)
        out, new_cache = _attn_sublayer(p["attn"], h, ctx, window=window, cache=cache)
        x = x + out
        if desc.cross:
            hx = rms_norm(x, p["lnx"], eps)
            # cross K/V ride in the same per-layer cache dict
            outx, cross_cache = _attn_sublayer(
                p["xattn"], hx, ctx, window=0, cache=cache, prefix="c", cross=True
            )
            x = x + outx
            if cross_cache is not None:
                new_cache = {**new_cache, **cross_cache}

    h = rms_norm(x, p["ln2"], eps)
    out, aux = _ffn_sublayer(p, h, desc, ctx)
    x = x + out
    x = ctx.shard(x, ("act_batch", "act_seq", "act_embed"))
    return x, aux, (new_cache if ctx.mode != "train" else None)


def _remat_policy(name: str):
    pols = jax.checkpoint_policies
    return {
        "none": pols.everything_saveable,
        "dots": pols.dots_with_no_batch_dims_saveable,
        "full": pols.nothing_saveable,
    }[name]


def apply_stack(params, x, ctx: Ctx, *, caches=None, windows=None):
    """Run the scanned group stack.

    params: stacked stack params; caches: stacked cache tree (decode) or None;
    windows: (num_layers,) int32 or None. Returns (x, aux_loss, new_caches).

    Decode reads each group's caches out of ``caches`` and has its layers
    emit only what they wrote (token rows, replaced states);
    ``_write_decode_cache`` puts that into ``caches`` after the layers, so no
    layer's whole cache is rebuilt and no fresh stack is built beside the
    donated one.
    """
    arch = ctx.arch
    period = structural_period(arch)
    n_grp = num_groups(arch)
    descs = layer_descs(arch)[:period]
    dyn_window = has_dynamic_window(arch)
    if windows is None:
        windows = windows_array(arch)
    win_grp = windows.reshape(n_grp, period)

    def group_body(x_in, gparams, gwin, gcache):
        new_gcache = {}
        aux_total = 0.0
        for j, desc in enumerate(descs):
            lcache = gcache.get(f"l{j}") if gcache is not None else None
            w = gwin[j] if dyn_window else 0
            x_in, aux, nc = apply_layer(
                gparams[f"l{j}"], x_in, desc, ctx, window=w, cache=lcache
            )
            aux_total = aux_total + aux
            if nc is not None:
                new_gcache[f"l{j}"] = nc
        return x_in, aux_total, (new_gcache or None)

    if ctx.run.scan_layers and n_grp > 1:
        def body(carry, scanned):
            x_c, aux_c = carry
            gparams, gwin, gi = scanned
            x_c, aux, nc = group_body(x_c, gparams, gwin, _group_cache(caches, gi))
            return (x_c, aux_c + aux), nc

        if ctx.mode == "train":
            body = jax.checkpoint(body, policy=_remat_policy(ctx.run.remat_policy), prevent_cse=True)
        xs = (params, win_grp, None if caches is None else jnp.arange(n_grp))
        (x, aux), new_caches = jax.lax.scan(body, (x, 0.0), xs)
        if ctx.mode == "decode":
            new_caches = _write_decode_cache(caches, new_caches, ctx.cache_len)
        return x, aux, new_caches

    # Unrolled path (exact per-layer cost analysis; scan_layers=False).
    body_fn = group_body
    if ctx.mode == "train":
        body_fn = jax.checkpoint(
            group_body, policy=_remat_policy(ctx.run.remat_policy), prevent_cse=True
        )
    aux_total = 0.0
    new_caches = []
    for gi in range(n_grp):
        gparams = jax.tree.map(lambda a: a[gi], params)
        x, aux, nc = body_fn(x, gparams, win_grp[gi], _group_cache(caches, gi))
        aux_total = aux_total + aux
        new_caches.append(nc)
    if new_caches and new_caches[0] is not None:
        new_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *new_caches)
    else:
        new_caches = None
    if ctx.mode == "decode":
        new_caches = _write_decode_cache(caches, new_caches, ctx.cache_len)
    return x, aux_total, new_caches


def apply_encoder(params, x, ctx: Ctx):
    """Whisper-style bidirectional encoder over frame embeddings (B, F, D)."""
    arch = ctx.arch
    desc = LayerDesc("attn", False, False)
    b, f, _ = x.shape
    enc_ctx = Ctx(
        arch=arch, run=ctx.run, mode="train",
        positions=jnp.broadcast_to(jnp.arange(f, dtype=jnp.int32)[None], (b, f)),
        shard=ctx.shard, interpret=ctx.interpret,
    )

    def body(carry, gparams):
        h = rms_norm(carry, gparams["l0"]["ln1"], arch.norm_eps)
        out, _ = _attn_sublayer(
            gparams["l0"]["attn"], h, enc_ctx, window=0, cache=None, causal=False
        )
        carry = carry + out
        h2 = rms_norm(carry, gparams["l0"]["ln2"], arch.norm_eps)
        with jax.named_scope("mlp"):
            carry = carry + gated_mlp(gparams["l0"]["mlp"], h2, enc_ctx.compute_dtype)
        return carry, None

    x, _ = jax.lax.scan(body, x, params, unroll=not ctx.run.scan_layers)
    return x


def sinusoidal_positions(seq: int, d: int, dtype) -> jnp.ndarray:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1).astype(dtype)
