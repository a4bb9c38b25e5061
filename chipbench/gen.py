"""Everything a run makes from ``--seed``: weights, prompts, patch and frame
stand-ins, and the training batches.

The engines feed what is made here to the program, and the references make
the same values again from the seed, so neither side takes anything from the
other. Each weight leaf is drawn from a key folded from the seed, the leaf's
path and (for a stacked leaf) the layer index, so a reference can draw one
layer of one leaf without drawing the rest.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

# Standard deviations by leaf name. Matrices are drawn at 1/sqrt(fan_in) of
# the dimension they contract, so every layer keeps activations near unit
# scale; norm scales are zero-centred (1 + w) and drawn small, so the norm's
# convention is part of what the references check.
NORM_STD = 0.1
EMBED_STD = 1.0
STUB_STD = 1.0  # patch stand-ins of the stub vision frontend


def seed_key(seed: int):
    """A key for any whole seed up to 64 bits."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def _path_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def leaf_std(path: str, shape) -> float:
    name = path.rsplit("/", 1)[-1]
    if name.startswith("ln") or name.endswith("norm"):
        return NORM_STD
    if name in ("embed", "unembed"):
        # the output head keeps logits near unit scale
        return EMBED_STD if name == "embed" else shape[-1] ** -0.5
    return shape[0] ** -0.5


def draw(key, path: str, shape, dtype):
    """One unstacked leaf."""
    x = jax.random.normal(_path_key(key, path), shape, jnp.float32)
    return (x * leaf_std(path, shape)).astype(dtype)


def draw_layer(key, path: str, layer: int, shape, dtype):
    """Layer ``layer`` of a stacked leaf whose per-layer shape is ``shape``."""
    return draw(jax.random.fold_in(key, layer), path, shape, dtype)


def path_str(path) -> str:
    """A tree path as the program's parameter path, ``stack/l0/attn/wq``."""
    return "/".join(str(getattr(p, "key", p)) for p in path)


STACKED = ("stack/", "encoder/")


def tree(key, abstract):
    """Every leaf of ``abstract`` (ShapeDtypeStructs keyed by the program's
    parameter paths), in its own dtype; traceable."""

    def leaf(path, a):
        p = path_str(path)
        if p.startswith(STACKED):
            layers = jnp.arange(a.shape[0])
            return jax.vmap(lambda i: draw(jax.random.fold_in(key, i), p,
                                           a.shape[1:], a.dtype))(layers)
        return draw(key, p, a.shape, a.dtype)

    return jax.tree_util.tree_map_with_path(leaf, abstract)


def weights(key, abstract, shardings=None):
    """The whole parameter tree, made by one jit on the device, each leaf
    with its sharding."""
    return jax.jit(lambda k: tree(k, abstract), out_shardings=shardings)(key)


# ------------------------------------------------------------------ serving


def prompt_tokens(seed: int, request: int, prompt_len: int, image_tokens: int,
                  vocab: int) -> np.ndarray:
    """(prompt_len,) ids drawn on the host; the image positions hold 0, which
    the patch stand-ins replace."""
    rng = np.random.default_rng((seed, request))
    toks = np.zeros(prompt_len, np.int32)
    toks[image_tokens:] = rng.integers(0, vocab, prompt_len - image_tokens,
                                       dtype=np.int32)
    return toks


def _patches(key, requests, n: int, d: int):
    def one(r):
        k = jax.random.fold_in(_path_key(key, "patches"), r)
        return (STUB_STD * jax.random.normal(k, (n, d), jnp.float32)).astype(jnp.bfloat16)

    return jax.vmap(one)(requests)


patches = jax.jit(_patches, static_argnums=(2, 3))
"""(seed key, (B,) request ids, n, d) -> (B, n, d) bf16 patch stand-ins,
made on the device."""


# ----------------------------------------------------------------- training


def train_batch(seed: int, step: int, batch: int, seq: int, frames: int, d: int,
                vocab: int, zipf_a: float = 1.2):
    """The batch of ``step`` for a one-host run, by the recipe of the
    program's synthetic pipeline (a zipfian id stream and 0.02-scaled normal
    frames from ``default_rng((seed, step, host))``), written out here so the
    reference draws it without the program."""
    rng = np.random.default_rng((seed, step, 0))
    u = rng.random((batch, seq + 1))
    ranks = u ** (-1.0 / (zipf_a - 1.0))
    ranks = np.nan_to_num(ranks, posinf=float(vocab))
    toks = np.minimum(ranks, vocab - 1).astype(np.int32)
    out = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    out["frames"] = rng.standard_normal((batch, frames, d), dtype=np.float32) * 0.02
    return out
