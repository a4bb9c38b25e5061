"""Plain float32 reference of the Whisper encoder-decoder as the configuration
states it, with its loss, gradients and AdamW steps.

Encoder: sinusoidal positions added to the frame embeddings, then
bidirectional pre-norm layers. Decoder: token embeddings, pre-norm layers of
causal self-attention, cross-attention over the encoder output and an MLP,
a final norm and an untied head; the loss is the mean cross-entropy over
every target. The configuration's departures from Whisper (SwiGLU, RMSNorm
with a zero-centred scale, rotary positions on self-attention, no biases)
are followed. Weights are drawn from the seed by ``chipbench.gen`` under the
same leaf paths the program's parameter tree has, so gradients and changes
can be compared leaf by leaf. Nothing of the program is imported.

``quant`` is applied to both operands of every matrix product and
``grad_quant`` to the cotangent of every projection's output: identities for
the reference; for the control, float8 as fp8 training uses it (e4m3
operands in the forward pass, e5m2 cotangents in the backward pass, one scale
per tensor).
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import gen
from chipbench.reference.dense_lm import attention, identity, rms_norm, rope


def straight_through(q):
    """Rounds in the forward pass, passes the cotangent unchanged."""
    return lambda x: x + jax.lax.stop_gradient(q(x) - x)


def fp8_e5m2(x):
    s = jnp.max(jnp.abs(x)) / 57344.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e5m2).astype(jnp.float32) * s


@jax.custom_vjp
def fp8_cotangent(y):
    """The identity, whose cotangent is rounded to e5m2."""
    return y


fp8_cotangent.defvjp(lambda y: (y, None), lambda _, g: (fp8_e5m2(g),))


def sinusoids(n: int, d: int):
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def leaf_shapes(cfg: dict):
    """{path: (layers or 0, shape)} in the program's parameter layout."""
    d, hq, hkv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, ff = cfg.get("head_dim") or d // hq, cfg["intermediate_size"]
    vpad = -(-cfg["vocab_size"] // 256) * 256
    attn = lambda p: {f"{p}wq": (d, hq * dh), f"{p}wk": (d, hkv * dh),
                      f"{p}wv": (d, hkv * dh), f"{p}wo": (hq * dh, d)}
    mlp = {"wi_gate": (d, ff), "wi_up": (d, ff), "wo": (ff, d)}
    out = {"embed": (0, (vpad, d)), "unembed": (0, (vpad, d)),
           "final_norm": (0, (d,)), "enc_final_norm": (0, (d,))}
    for root, n, extra in (("encoder/l0/", cfg["encoder_layers"], False),
                           ("stack/l0/", cfg["decoder_layers"], True)):
        leaves = {f"attn/{k}": s for k, s in attn("").items()}
        leaves.update({f"mlp/{k}": s for k, s in mlp.items()})
        leaves.update({"ln1": (d,), "ln2": (d,)})
        if extra:
            leaves.update({f"xattn/{k}": s for k, s in attn("c").items()})
            leaves["lnx"] = (d,)
        out.update({root + k: (n, s) for k, s in leaves.items()})
    return out


def init(cfg: dict, key, dtype=jnp.float32):
    """The parameters as {path: array}, stacked leaves with a leading layer
    axis."""

    def make(key):
        out = {}
        for p, (n, s) in leaf_shapes(cfg).items():
            if n:
                out[p] = jnp.stack([gen.draw_layer(key, p, i, s, dtype) for i in range(n)])
            else:
                out[p] = gen.draw(key, p, s, dtype)
        return out

    return jax.jit(make)(key)


def loss_sum(params, batch, cfg, quant=identity, grad_quant=identity):
    """Summed cross-entropy over every target of the rows in ``batch``."""
    d, hq, hkv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // hq
    eps, theta, V = cfg["rms_norm_eps"], cfg["rope_theta"], cfg["vocab_size"]
    mm = lambda a, w: grad_quant(jnp.einsum("btd,de->bte", quant(a), quant(w)))

    def attn(w, p, h, kv_in, *, causal, rotary):
        b, t, _ = h.shape
        q = mm(h, w[p + "wq"]).reshape(b, t, hq, dh)
        k = mm(kv_in, w[p + "wk"]).reshape(b, kv_in.shape[1], hkv, dh)
        v = mm(kv_in, w[p + "wv"]).reshape(b, kv_in.shape[1], hkv, dh)
        if rotary:
            q, k = rope(q, jnp.arange(t), theta), rope(k, jnp.arange(t), theta)
        o = attention(q, k, v, causal=causal, quant=quant)
        return mm(o.reshape(b, t, hq * dh), w[p + "wo"])

    def mlp(w, root, h):
        g = jax.nn.silu(mm(h, w[root + "mlp/wi_gate"])) * mm(h, w[root + "mlp/wi_up"])
        return mm(g, w[root + "mlp/wo"])

    def layer(params, root, i):
        return {k[len(root):]: v[i] for k, v in params.items() if k.startswith(root)}

    frames = batch["frames"]
    x = frames + sinusoids(frames.shape[1], d)[None]
    for i in range(cfg["encoder_layers"]):
        w = layer(params, "encoder/l0/", i)
        h = rms_norm(x, w["ln1"], eps)
        x = x + attn(w, "attn/", h, h, causal=False, rotary=True)
        x = x + mlp(w, "", rms_norm(x, w["ln2"], eps))
    enc = rms_norm(x, params["enc_final_norm"], eps)

    x = params["embed"][batch["tokens"]]
    for i in range(cfg["decoder_layers"]):
        w = layer(params, "stack/l0/", i)
        h = rms_norm(x, w["ln1"], eps)
        x = x + attn(w, "attn/", h, h, causal=True, rotary=True)
        x = x + attn(w, "xattn/c", rms_norm(x, w["lnx"], eps), enc, causal=False, rotary=False)
        x = x + mlp(w, "", rms_norm(x, w["ln2"], eps))
    h = rms_norm(x, params["final_norm"], eps)
    logits = grad_quant(jnp.einsum("btd,vd->btv", quant(h), quant(params["unembed"][:V])))
    lse = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.sum(lse - ll)


def leaf_norms(tree: dict) -> dict:
    return {p: float(jnp.sqrt(jnp.sum(jnp.square(x)))) for p, x in tree.items()}


@functools.lru_cache(maxsize=None)
def _grad_fn(cfg_json: str, quant, grad_quant):
    cfg = json.loads(cfg_json)
    return jax.jit(jax.value_and_grad(
        lambda params, batch: loss_sum(params, batch, cfg, quant, grad_quant)))


@functools.lru_cache(maxsize=None)
def _adamw(opt_json: str):
    """One AdamW step over {path: array} trees, as the configuration states
    it (bias-corrected moments, decoupled weight decay on every leaf)."""
    o = json.loads(opt_json)

    def step_fn(params, mu, nu, grads, step, lr):
        t = step + 1.0
        new = {}, {}, {}
        for p in params:
            g = grads[p]
            m = o["b1"] * mu[p] + (1 - o["b1"]) * g
            v = o["b2"] * nu[p] + (1 - o["b2"]) * g * g
            delta = (m / (1 - o["b1"] ** t)) / (jnp.sqrt(v / (1 - o["b2"] ** t)) + o["eps"])
            new[0][p] = params[p] - lr * (delta + o["weight_decay"] * params[p])
            new[1][p], new[2][p] = m, v
        return new

    return jax.jit(step_fn)


class Trainer:
    """Three (or more) AdamW steps of the reference on given host batches,
    each computed over blocks of rows so that it fits beside nothing else."""

    def __init__(self, cfg: dict, key, quant=identity, grad_quant=identity,
                 rows_per_block: int = 4):
        self.cfg, self.rows = cfg, rows_per_block
        self.opt = cfg["optimizer"]
        self.params = init(cfg, key)
        self.p0 = dict(self.params)
        self.mu = {p: jnp.zeros_like(x) for p, x in self.params.items()}
        self.nu = {p: jnp.zeros_like(x) for p, x in self.params.items()}
        self._grad = _grad_fn(json.dumps(cfg, sort_keys=True), quant, grad_quant)
        self._adamw = _adamw(json.dumps(self.opt, sort_keys=True))

    def lr(self, step: int) -> float:
        o = self.opt
        return o["peak_lr"] * min(step / max(o["warmup_steps"], 1), 1.0)

    def loss_and_grads(self, batch: dict):
        """Mean loss and gradients over the whole batch, block by block."""
        n = batch["tokens"].size
        total, grads = 0.0, None
        with jax.default_matmul_precision("highest"):
            for lo in range(0, batch["tokens"].shape[0], self.rows):
                blk = {k: jnp.asarray(v[lo:lo + self.rows]) for k, v in batch.items()}
                loss, g = self._grad(self.params, blk)
                total += float(loss)
                grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        return total / n, jax.tree.map(lambda g: g / n, grads)

    def step(self, step: int, batch: dict):
        """One clipped AdamW step; returns (loss, the clipped gradients)."""
        loss, grads = self.loss_and_grads(batch)
        norm = math.sqrt(sum(float(jnp.sum(g * g)) for g in grads.values()))
        scale = min(1.0, self.opt["clip_global_norm"] / max(norm, 1e-9))
        grads = {p: g * scale for p, g in grads.items()}
        with jax.default_matmul_precision("highest"):
            self.params, self.mu, self.nu = self._adamw(
                self.params, self.mu, self.nu, grads, float(step), self.lr(step))
        return loss, grads

    def change_norms(self) -> dict:
        return leaf_norms({p: self.params[p] - self.p0[p] for p in self.params})


def readings(cfg: dict, key, batches, quant=identity, grad_quant=identity,
             rows_per_block: int = 4):
    """What the program's first steps are compared with: each step's loss,
    the norm of every leaf of the first (clipped) gradient, and of every
    leaf's change after all the steps."""
    tr = Trainer(cfg, key, quant, grad_quant, rows_per_block)
    losses, first = [], None
    for s, b in enumerate(batches):
        loss, g = tr.step(s, b)
        losses.append(loss)
        if first is None:
            first = leaf_norms(g)
    return {"losses": losses, "grad_norms": first, "change_norms": tr.change_norms()}


def leaf_gaps(prog: dict, ref: dict, keys) -> list:
    """Per leaf, the gap between the two sides' norms against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    if set(prog) != set(ref):
        return [math.inf]  # the trees differ: no leaf-by-leaf comparison holds
    med = float(np.median([ref[k] for k in keys]))
    return [abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in sorted(keys)]
