"""Plain float32 reference of a dense decoder-only LM with a stub vision
frontend: the InternLM2 backbone of InternVL2.

Written from the published description (pre-norm RMSNorm, rotary positions
with rotate-half, grouped-query attention, SwiGLU MLP, untied head) plus the
departures the configuration lists (zero-centred norm scale, the first
``image_tokens`` positions replaced by patch embeddings). It imports nothing
of the program: the weights are drawn again from the seed by
``chipbench.gen``, one layer at a time, in the dtype they are served in and
then widened to float32. Every matrix product runs at ``highest`` precision.

``quant`` is applied to both operands of every matrix product: the identity
for the reference, a rounding to a lower precision for the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import gen

Q_CHUNK = 1024  # query rows per attention block, so scores fit beside weights


def identity(x):
    return x


def fp8(x):
    """Round to float8_e4m3fn with one scale per tensor, back to float32."""
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def rope(x, pos, theta):
    """x (B, T, H, Dh), rotate-half convention."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv  # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, *, causal, quant):
    """q (B, S, Hq, Dh) at positions ``T - S ..`` of k, v (B, T, Hkv, Dh)."""
    b, s, hq, dh = q.shape
    t, hkv = k.shape[1], k.shape[2]
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    outs = []
    for lo in range(0, s, Q_CHUNK):
        qc = q[:, lo:lo + Q_CHUNK]
        sc = jnp.einsum("bshd,bthd->bhst", quant(qc), quant(k)) * dh ** -0.5
        if causal:
            qpos = (t - s + lo + jnp.arange(qc.shape[1]))[:, None]
            sc = jnp.where(jnp.arange(t)[None, :] <= qpos, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("bhst,bthd->bshd", quant(p), quant(v)))
    return jnp.concatenate(outs, axis=1)


class DenseLM:
    """``logits(tokens, patches, first)`` gives the float32 logits at
    positions ``first ..`` of each row, over the unpadded vocabulary."""

    def __init__(self, cfg: dict, key, quant=identity, dtype=jnp.bfloat16):
        self.cfg, self.key, self.quant, self.dtype = cfg, key, quant, dtype
        d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
        hkv, ff = cfg["num_key_value_heads"], cfg["intermediate_size"]
        dh = cfg.get("head_dim") or d // hq
        self.vpad = -(-cfg["vocab_size"] // 256) * 256
        self.shapes = {
            "attn/wq": (d, hq * dh), "attn/wk": (d, hkv * dh),
            "attn/wv": (d, hkv * dh), "attn/wo": (hq * dh, d),
            "ln1": (d,), "ln2": (d,),
            "mlp/wi_gate": (d, ff), "mlp/wi_up": (d, ff), "mlp/wo": (ff, d),
        }
        self._layer_w = jax.jit(self._draw_layer)
        self._layer = jax.jit(self._apply_layer)
        self._embed = jax.jit(self._embed_fn)
        self._head = jax.jit(self._head_fn, static_argnums=1)

    def _draw_layer(self, i):
        return {n: gen.draw_layer(self.key, "stack/l0/" + n, i, s, self.dtype)
                .astype(jnp.float32) for n, s in self.shapes.items()}

    def _embed_fn(self, tokens, patches):
        table = gen.draw(self.key, "embed", (self.vpad, self.cfg["hidden_size"]), self.dtype)
        x = table[tokens].astype(jnp.float32)
        return x.at[:, : patches.shape[1]].set(patches.astype(jnp.float32))

    def _apply_layer(self, w, x):
        cfg, q_ = self.cfg, self.quant
        b, t, d = x.shape
        hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        dh = cfg.get("head_dim") or d // hq
        eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
        pos = jnp.arange(t)
        h = rms_norm(x, w["ln1"], eps)
        mm = lambda a, m: jnp.einsum("btd,de->bte", q_(a), q_(m))
        q = rope(mm(h, w["attn/wq"]).reshape(b, t, hq, dh), pos, theta)
        k = rope(mm(h, w["attn/wk"]).reshape(b, t, hkv, dh), pos, theta)
        v = mm(h, w["attn/wv"]).reshape(b, t, hkv, dh)
        o = attention(q, k, v, causal=True, quant=q_).reshape(b, t, hq * dh)
        x = x + mm(o, w["attn/wo"])
        h = rms_norm(x, w["ln2"], eps)
        g = jax.nn.silu(mm(h, w["mlp/wi_gate"])) * mm(h, w["mlp/wi_up"])
        return x + mm(g, w["mlp/wo"])

    def _head_fn(self, x, first):
        cfg = self.cfg
        norm = gen.draw(self.key, "final_norm", (cfg["hidden_size"],), self.dtype)
        table = gen.draw(self.key, "unembed", (self.vpad, cfg["hidden_size"]), self.dtype)
        h = rms_norm(x[:, first:], norm.astype(jnp.float32), cfg["rms_norm_eps"])
        w = table[: cfg["vocab_size"]].astype(jnp.float32)
        return jnp.einsum("btd,vd->btv", self.quant(h), self.quant(w))

    def logits(self, blocks, first: int) -> list:
        """Per block of rows ``(tokens, patches)``, the float32 logits at
        positions ``first ..`` over the unpadded vocabulary. Layer by layer:
        each layer's weights are drawn once and applied to every block."""
        with jax.default_matmul_precision("highest"):
            xs = [self._embed(tokens, patches) for tokens, patches in blocks]
            for i in range(self.cfg["num_hidden_layers"]):
                w = self._layer_w(i)
                xs = [self._layer(w, x) for x in xs]
                del w
            return [self._head(x, first) for x in xs]


@jax.jit
def served_gaps(ref_logits, tokens):
    """Per position, how far the reference's logit of the token put there
    lies below the reference's best."""
    chosen = jnp.take_along_axis(ref_logits, tokens[..., None], -1)[..., 0]
    return jnp.max(ref_logits, -1) - chosen
