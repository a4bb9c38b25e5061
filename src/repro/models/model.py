"""Model facade: ``ArchConfig × RunConfig → init / loss / prefill / decode``.

Everything the launcher, tuner, and dry-run need from a model:

  - ``param_specs()``      — declarative PSpec tree (shapes + logical axes)
  - ``abstract_params()``  — ShapeDtypeStruct tree (AOT dry-run, no allocation)
  - ``init_params(rng)``   — real arrays (smoke tests / examples)
  - ``loss(params, batch)``        — train-mode forward + CE loss
  - ``prefill(params, batch)``     — full-sequence forward, emits caches
  - ``decode_step(params, caches, batch)`` — one-token step against caches
  - ``input_specs(shape)`` / ``cache_abstract(...)`` — dry-run stand-ins

The model is sharding-agnostic: it calls ``ctx.shard(x, logical_axes)`` at
layer boundaries and the caller provides the logical→mesh rules (see
``repro.distributed.sharding``). With ``rules=None`` every constraint is a
no-op, so the same code runs on one CPU device.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, RunConfig, ShapeConfig
from repro.models import transformer as tfm
from repro.models.layers import (
    PSpec,
    abstract_params,
    cross_entropy,
    init_params,
    partition_specs,
    rms_norm,
    rms_norm_specs,
    softcap,
)

AUX_LOSS_WEIGHT = 0.01


def _noop_shard(x, axes):
    return x


def make_shard_fn(rules: Optional[Dict[str, Any]]):
    if rules is None:
        return _noop_shard
    from jax.sharding import PartitionSpec as P

    sizes = rules.get("_sizes", {})

    def axis_product(r) -> int:
        names = (r,) if isinstance(r, str) else tuple(r)
        n = 1
        for name in names:
            n *= sizes.get(name, 1)
        return n

    def shard(x, axes):
        mesh_axes = []
        used = set()
        for i, a in enumerate(axes):
            r = rules.get(a) if a is not None else None
            if r is not None and x.shape[i] % axis_product(r) != 0:
                r = None  # dimension not divisible: leave unconstrained
            if r is not None:
                names = (r,) if isinstance(r, str) else tuple(r)
                if any(n in used for n in names):
                    r = None  # a mesh axis may shard only one dim (e.g. seq-
                else:        # parallel residual + head-sharded qkv)
                    used.update(names)
            mesh_axes.append(r)
        return jax.lax.with_sharding_constraint(x, P(*mesh_axes))

    return shard


@dataclass
class Model:
    arch: ArchConfig
    run: RunConfig

    # ------------------------------------------------------------------ params

    def param_specs(self) -> Dict[str, Any]:
        arch = self.arch
        d = arch.d_model
        specs: Dict[str, Any] = {
            "embed": PSpec((arch.padded_vocab, d), ("vocab", "embed"), init="small_normal"),
            "stack": tfm.stack_specs(arch),
            "final_norm": rms_norm_specs(d),
        }
        if not arch.tie_embeddings:
            specs["unembed"] = PSpec((arch.padded_vocab, d), ("vocab", "embed"), init="small_normal")
        if arch.encoder_layers:
            specs["encoder"] = tfm.encoder_stack_specs(arch)
            specs["enc_final_norm"] = rms_norm_specs(d)
        return specs

    def abstract_params(self, dtype=None):
        return abstract_params(self.param_specs(), jnp.dtype(dtype or self.run.param_dtype))

    def init_params(self, rng, dtype=None):
        return init_params(self.param_specs(), rng, jnp.dtype(dtype or self.run.param_dtype))

    def param_partition_specs(self, rules: Dict[str, Any]):
        return partition_specs(self.param_specs(), rules)

    # ------------------------------------------------------------------ caches

    def cache_capacity(self, shape: ShapeConfig) -> int:
        return shape.seq_len

    def cache_specs(self, batch: int, capacity: int) -> Dict[str, Any]:
        return tfm.cache_specs(self.arch, batch, capacity, self.run)

    def cache_abstract(self, batch: int, capacity: int):
        spec_tree = self.cache_specs(batch, capacity)
        dtypes = tfm.cache_dtypes(self.arch, self.run, spec_tree)
        return jax.tree.map(
            lambda s, dt: jax.ShapeDtypeStruct(s.shape, dt),
            spec_tree,
            dtypes,
            is_leaf=lambda x: isinstance(x, PSpec),
        )

    def cache_init(self, batch: int, capacity: int):
        spec_tree = self.cache_specs(batch, capacity)
        dtypes = tfm.cache_dtypes(self.arch, self.run, spec_tree)
        return jax.tree.map(
            lambda s, dt: jnp.ones(s.shape, dt) if s.init == "ones" else jnp.zeros(s.shape, dt),
            spec_tree,
            dtypes,
            is_leaf=lambda x: isinstance(x, PSpec),
        )

    def cache_partition_specs(self, rules: Dict[str, Any], batch: int, capacity: int):
        return partition_specs(self.cache_specs(batch, capacity), rules)

    # ----------------------------------------------------------------- forward

    def _embed_inputs(self, params, batch, ctx: tfm.Ctx):
        """Token embeddings + modality-frontend substitution (named scope
        ``embed``)."""
        arch = self.arch
        cd = ctx.compute_dtype
        tokens = batch["tokens"]
        with jax.named_scope("embed"):
            if self.run.embed_impl == "one_hot" and ctx.mode == "train":
                # iota one-hot matmul: the vocab axis stays sharded and the
                # backward pass is a matmul (no scatter-add into the table).
                onehot = jax.nn.one_hot(tokens, arch.padded_vocab, dtype=cd)
                x = jnp.einsum("bsv,vd->bsd", onehot, params["embed"].astype(cd))
            else:
                x = params["embed"].astype(cd)[tokens]
            x = x * jnp.asarray(arch.d_model, cd) ** 0.5 if arch.tie_embeddings else x
            if arch.frontend == "vision" and "patches" in batch:
                p = batch["patches"].astype(cd)  # (B, P, D) precomputed (stub)
                x = jax.lax.dynamic_update_slice(x, p, (0, 0, 0))
            return x

    def _encode(self, params, batch, ctx: tfm.Ctx):
        """The audio encoder over the frames (named scope ``encoder``)."""
        with jax.named_scope("encoder"):
            frames = batch["frames"].astype(ctx.compute_dtype)  # (B, F, D) stub
            pos = tfm.sinusoidal_positions(frames.shape[1], self.arch.d_model, frames.dtype)
            enc = tfm.apply_encoder(params["encoder"], frames + pos[None], ctx)
            return rms_norm(enc, params["enc_final_norm"], self.arch.norm_eps)

    def _logits(self, params, x, ctx: tfm.Ctx):
        """Logits stay in compute dtype (bf16): the CE converts to f32 inside
        its (fusable) reductions, avoiding a materialized f32 (B,S,V) buffer.
        Named scope ``logits``."""
        arch = self.arch
        table = params["embed"] if arch.tie_embeddings else params["unembed"]
        with jax.named_scope("logits"):
            logits = jnp.einsum("bsd,vd->bsv", x, table.astype(ctx.compute_dtype))
            return softcap(logits, arch.final_logit_softcap)

    def _cast_params(self, params, ctx: tfm.Ctx):
        """Pre-cast the whole tree to compute dtype ONCE, outside the layer
        scan. With FSDP/2D-sharded weights this moves the per-layer weight
        all-gathers from f32 masters to bf16 — half the wire bytes of the
        dominant collective term in FSDP training (§Perf iteration 3)."""
        cd = ctx.compute_dtype

        def cast(w):
            # int8 serving weights keep their per-layer (fused) dequant; only
            # wider floats are narrowed upfront
            if jnp.issubdtype(w.dtype, jnp.floating) and jnp.dtype(w.dtype).itemsize > cd.itemsize:
                return w.astype(cd)
            return w

        return jax.tree.map(cast, params)

    def _backbone(self, params, x, ctx: tfm.Ctx, caches=None):
        x = ctx.shard(x, ("act_batch", "act_seq", "act_embed"))
        x, aux, new_caches = tfm.apply_stack(params["stack"], x, ctx, caches=caches)
        x = rms_norm(x, params["final_norm"], self.arch.norm_eps)
        return x, aux, new_caches

    def _make_ctx(self, mode: str, positions, rules, cache_len=None, enc_out=None,
                  interpret=False) -> tfm.Ctx:
        return tfm.Ctx(
            arch=self.arch, run=self.run, mode=mode, positions=positions,
            shard=make_shard_fn(rules), cache_len=cache_len, enc_out=enc_out,
            interpret=interpret,
        )

    # ------------------------------------------------------------------- train

    def loss(self, params, batch, *, rules=None, interpret=False):
        """batch: tokens (B,S), labels (B,S), [patches|frames]. Returns
        (loss, metrics)."""
        arch = self.arch
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        ctx = self._make_ctx("train", positions, rules, interpret=interpret)
        params = self._cast_params(params, ctx)
        enc_out = None
        if arch.encoder_layers:
            enc_out = self._encode(params, batch, ctx)
            ctx.enc_out = enc_out
        x = self._embed_inputs(params, batch, ctx)
        x, aux, _ = self._backbone(params, x, ctx)
        logits = self._logits(params, x, ctx)
        with jax.named_scope("loss"):
            labels = batch["labels"]
            if arch.frontend == "vision":
                # vision positions carry no next-token target
                labels = jnp.where(positions < arch.frontend_seq, -1, labels)
            ce = cross_entropy(logits, labels, arch.vocab_size)
            loss = ce + AUX_LOSS_WEIGHT * aux
        return loss, {"ce": ce, "aux": aux}

    # ------------------------------------------------------------------- serve

    def prefill(self, params, batch, *, rules=None, interpret=False):
        """Full-sequence forward; returns (last-token logits (B, V), caches)."""
        arch = self.arch
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        ctx = self._make_ctx("prefill", positions, rules, interpret=interpret)
        params = self._cast_params(params, ctx)
        if arch.encoder_layers:
            ctx.enc_out = self._encode(params, batch, ctx)
        x = self._embed_inputs(params, batch, ctx)
        x, _, caches = self._backbone(params, x, ctx)
        logits = self._logits(params, x[:, -1:, :], ctx)
        return logits[:, 0], caches

    def decode_step(self, params, caches, batch, *, rules=None, interpret=False):
        """One decode step. batch: tokens (B,1), cache_len scalar int32.
        Returns (logits (B, V), new caches)."""
        tokens = batch["tokens"]
        b = tokens.shape[0]
        cache_len = batch["cache_len"]
        positions = jnp.broadcast_to(cache_len[None, None], (b, 1)).astype(jnp.int32)
        ctx = self._make_ctx("decode", positions, rules, cache_len=cache_len,
                             interpret=interpret)
        params = self._cast_params(params, ctx)
        x = self._embed_inputs(params, batch, ctx)
        x, _, new_caches = self._backbone(params, x, ctx, caches=caches)
        logits = self._logits(params, x, ctx)
        return logits[:, 0], new_caches

    # ----------------------------------------------------------------- dry-run

    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input of this cell."""
        arch = self.arch
        b, s = shape.global_batch, shape.seq_len
        tok = jax.ShapeDtypeStruct
        if shape.kind == "train":
            batch = {
                "tokens": tok((b, s), jnp.int32),
                "labels": tok((b, s), jnp.int32),
            }
        elif shape.kind == "prefill":
            batch = {"tokens": tok((b, s), jnp.int32)}
        else:  # decode
            batch = {
                "tokens": tok((b, 1), jnp.int32),
                "cache_len": tok((), jnp.int32),
            }
        if shape.kind != "decode":
            if arch.frontend == "vision":
                batch["patches"] = tok((b, arch.frontend_seq, arch.d_model), jnp.bfloat16)
            elif arch.frontend == "audio":
                batch["frames"] = tok((b, arch.frontend_seq, arch.d_model), jnp.bfloat16)
        return batch

    def make_inputs(self, shape: ShapeConfig, rng=None):
        """Real (synthetic) inputs matching ``input_specs`` (smoke tests)."""
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        specs = self.input_specs(shape)
        out = {}
        for name, sds in specs.items():
            rng, sub = jax.random.split(rng)
            if name in ("tokens", "labels"):
                out[name] = jax.random.randint(sub, sds.shape, 0, self.arch.vocab_size, jnp.int32)
            elif name == "cache_len":
                out[name] = jnp.asarray(shape.seq_len - 1, jnp.int32)
            else:
                out[name] = 0.02 * jax.random.normal(sub, sds.shape, jnp.float32)
        return out
