"""Serving at mp=4, as ``ivl2_26b-caption-mp4`` runs InternVL2-26B over a
four-chip host, on four fake CPU devices.

The whole path the benchmark drives: ``make_prefill_step`` and
``make_decode_step`` on ``make_host_mesh(4)``, weights drawn sharded by
``chipbench.gen``, prefill, ``grow_caches``, then greedy decode steps. The
logits are compared with the plain float32 reference
(``chipbench/reference/dense_lm.py``), and the compiled programs' collectives
are counted. Head counts that divide by 4 (as 48/8 do) split attention and
the KV cache by head; the registry's smoke preset (4/2 heads) falls back to
splitting the cache's sequence.
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import scopes  # noqa: E402

# the smoke internvl2-26b with its heads and width replaced; 2 layers, d_ff
# 128, vocab 512, heads of 16
_SETUP = """
import dataclasses, re, sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp, numpy as np
from chipbench import gen, program
from chipbench.reference import dense_lm
from repro.configs.archs import get_arch
from repro.configs.base import RunConfig, ShapeConfig
from repro.distributed.steps import make_decode_step, make_prefill_step
from repro.launch.mesh import make_host_mesh
from repro.launch.serve import grow_caches

B, P, N, IMG = 4, 24, 8, 9
arch = dataclasses.replace(get_arch("internvl2-26b", smoke=True), num_heads={hq},
                           num_kv_heads={hkv}, d_model={d}, head_dim=16, frontend_seq=IMG)
cfg = {{"hidden_size": {d}, "num_hidden_layers": arch.num_layers,
       "num_attention_heads": {hq}, "num_key_value_heads": {hkv}, "head_dim": 16,
       "intermediate_size": arch.d_ff, "vocab_size": arch.vocab_size,
       "rope_theta": arch.rope_theta, "rms_norm_eps": arch.norm_eps}}
mesh = make_host_mesh(4, devices=jax.devices()[:4])
run = RunConfig(mesh_model_parallel=4)
pre = make_prefill_step(arch, run, ShapeConfig("p", P, B, "prefill"), mesh)
dec = make_decode_step(arch, run, ShapeConfig("d", P + N, B, "decode"), mesh)
"""

_SERVE = """
seed = 2**31 + 7
key = gen.seed_key(seed)
V = arch.vocab_size
with jax.set_mesh(mesh):
    params = gen.weights(key, pre.abstract_inputs[0], program.named(mesh, pre.in_shardings[0]))
    toks = np.stack([gen.prompt_tokens(seed, r, P, IMG, V) for r in range(B)])
    patches = gen.patches(key, jnp.arange(B, dtype=jnp.int32), IMG, {d})
    logits, caches = pre.jit()(params, {{"tokens": jnp.asarray(toks), "patches": patches}})
    caches = grow_caches(caches, N)
    grown = caches
    outs, served = [logits[:, :V].astype(jnp.float32)], []
    step = dec.jit(donate=False)
    for i in range(N - 1):
        tok = jnp.argmax(outs[-1], -1)[:, None].astype(jnp.int32)
        served.append(tok)
        logits, caches = step(params, caches, {{"tokens": tok,
                                                "cache_len": jnp.asarray(P + i, jnp.int32)}})
        outs.append(logits[:, :V].astype(jnp.float32))

# every matrix leaf keeps its split over the model axis: heads or columns of
# the projections, rows of the embedding and head; only the norm scales are
# whole on each device
for path, x in jax.tree_util.tree_leaves_with_path(params):
    p = gen.path_str(path)
    if x.ndim == 1 or p.rsplit("/", 1)[-1].startswith("ln"):
        continue
    spec = list(x.sharding.spec)
    assert "model" in spec, (p, x.sharding)
    axis = spec.index("model")
    assert x.addressable_shards[0].data.shape[axis] * 4 == x.shape[axis], p
# the grown cache keeps its split: by KV head, or by position where the KV
# heads do not divide by 4
for path, x in jax.tree_util.tree_leaves_with_path(grown):
    axis = {axis}
    assert x.shape[2] == P + N and x.sharding.spec[axis] == "model", (path, x.sharding)
    assert x.addressable_shards[0].data.shape[axis] * 4 == x.shape[axis], path

prog = np.asarray(jnp.stack(outs, 1))  # positions P-1 .. P+N-2
full = jnp.concatenate([jnp.asarray(toks)] + served, 1)
ref = np.asarray(dense_lm.DenseLM(cfg, key).logits([(full, patches)], P - 1)[0])
print("GAP", float(np.abs(prog - ref).max()), float(np.abs(ref).max()))
"""


@pytest.mark.parametrize("hq,hkv,d,axis", [
    (12, 4, 96, 3),  # heads divide by 4: the cache split by KV head
    (4, 2, 64, 2),   # the registry's smoke preset: the cache split by position
], ids=["kv-heads", "kv-sequence"])
def test_mp4_serving_matches_the_reference(subproc, repo_root, hq, hkv, d, axis):
    """Prefill, grow_caches and 7 greedy decode steps at mp=4 give the
    reference's logits at every position.

    Tolerance 0.08 on the largest logit error, over logits of magnitude ~4-5:
    the program stores weights, activations and the cache in bf16 (8
    significant bits) and the reference widens the same bf16 weights to
    float32, so two layers leave 0.03 (both cases). The reference in float8
    (e4m3, 4 significant bits) lies 0.32-0.36 from it, and logits rounded to
    e4m3 are off by up to 0.25 at that magnitude; both fail."""
    code = (_SETUP.format(root=str(repo_root), hq=hq, hkv=hkv, d=d)
            + _SERVE.format(d=d, axis=axis))
    out = subproc(code, devices=4)
    gap, scale = map(float, out.split("GAP", 1)[1].split())
    assert scale > 2.0  # logits of the magnitude the tolerance is set at
    assert gap <= 0.08, gap


_COLLECTIVES = r"""
texts = []
with jax.set_mesh(mesh):
    for b in (pre, dec):
        texts.append(jax.jit(b.fn, in_shardings=program.named(mesh, b.in_shardings),
                             out_shardings=program.named(mesh, b.out_shardings))
                     .lower(*b.abstract_inputs).compile().as_text())
# a collective's instruction, synchronous or the start of an asynchronous pair
kind = re.compile(r"\s(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
                  r"(?:-start)?\(")
for text in texts:
    for line in text.splitlines():
        m = kind.search(line)
        if m:
            op = re.search(r'op_name="([^"]*)"', line)
            print("COLLECTIVE", m.group(1), op.group(1) if op else "-")
    print("PROGRAM")
"""


def test_mp4_programs_hold_three_all_reduces(subproc, repo_root):
    """The compiled mp=4 prefill and decode programs each hold exactly three
    collectives, all all-reduces: the attention output and the MLP output in
    the layer scan's body, and the vocabulary-split embedding gather. No
    all-gather, all-to-all, reduce-scatter or collective-permute: a sharding
    rule that adds traffic fails here. The scopes pinned are those
    ``chipbench/scopes.py`` books the collectives under."""
    code = _SETUP.format(root=str(repo_root), hq=12, hkv=4, d=96) + _COLLECTIVES
    out = subproc(code, devices=4)
    programs = out.split("PROGRAM")[:2]
    for name, text in zip(("prefill_step", "decode_step"), programs):
        found = [line.split()[1:] for line in text.splitlines()
                 if line.startswith("COLLECTIVE")]
        assert [kind for kind, _ in found] == ["all-reduce"] * 3, found
        # each booked under its sublayer's scope; the two per layer inside the
        # layer scan's body
        booked = sorted((tuple(c for c in scopes.scope_components(op)
                               if c in scopes.MODEL_SCOPES),
                         "body" in op.split("/"), op.startswith(f"jit({name})/"))
                        for _, op in found)
        assert booked == [(("attn", "out"), True, True), (("embed",), False, True),
                          (("mlp",), True, True)], found
