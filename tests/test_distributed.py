"""Distributed behaviour on a fake 8-device world (subprocess: these tests
must not pollute the main process's single-device view).

Covers: (2,2,2) pod×data×model train execution, gradient-compression path
(numerics vs uncompressed + int8 wire in HLO), serve bundles, sharding-rule
divisibility fallbacks, and the production-mesh function itself.
"""
import pytest


def test_train_step_multi_pod_exec(subproc):
    out = subproc("""
import jax, jax.numpy as jnp
from repro.configs.base import ShapeConfig, RunConfig
from repro.configs.archs import get_arch
from repro.distributed.steps import make_step, init_train_state
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(model_parallel=2, pod=2)
arch = get_arch("llama3.2-1b", smoke=True)
shape = ShapeConfig("t", 32, 8, "train")
with jax.set_mesh(mesh):
    b = make_step(arch, RunConfig(mesh_model_parallel=2), shape, mesh)
    state = init_train_state(b)
    batch = b.model.make_inputs(shape)
    state, batch = b.place(mesh, state, batch)
    fn = b.jit()
    l0 = None
    for i in range(4):
        state, m = fn(state, batch)
        l0 = l0 if l0 is not None else float(m["loss"])
    assert float(m["loss"]) < l0, (float(m["loss"]), l0)
print("TRAIN_OK")
""")
    assert "TRAIN_OK" in out


def test_grad_compression_matches_uncompressed(subproc):
    out = subproc("""
import jax, jax.numpy as jnp
from repro.configs.base import ShapeConfig, RunConfig
from repro.configs.archs import get_arch
from repro.distributed.steps import make_step, init_train_state
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(model_parallel=2, pod=2)
arch = get_arch("llama3.2-1b", smoke=True)
shape = ShapeConfig("t", 32, 8, "train")
losses = {}
for comp in ["off", "int8"]:
    with jax.set_mesh(mesh):
        b = make_step(arch, RunConfig(mesh_model_parallel=2, grad_compression=comp), shape, mesh)
        state = init_train_state(b, jax.random.PRNGKey(0))
        batch = b.model.make_inputs(shape, jax.random.PRNGKey(1))
        state, batch = b.place(mesh, state, batch)
        fn = b.jit()
        for i in range(3):
            state, m = fn(state, batch)
        losses[comp] = float(m["loss"])
        if comp == "int8":
            txt = b.lower().compile().as_text()
            n_int = sum(1 for l in txt.splitlines() if "all-reduce" in l and ("s32[" in l or "s8[" in l))
            assert n_int > 0, "no int8/int32 cross-pod all-reduce in HLO"
rel = abs(losses["off"] - losses["int8"]) / abs(losses["off"])
assert rel < 0.02, losses  # error feedback keeps trajectories close
print("COMPRESS_OK", losses)
""")
    assert "COMPRESS_OK" in out


def test_serve_bundles_with_awkward_heads(subproc):
    """gemma3 (kv=1) and whisper (6 heads) on model_parallel=4: the rules must
    fall back (sequence-partition KV / replicate heads) and still execute."""
    out = subproc("""
import jax, jax.numpy as jnp
from repro.configs.base import ShapeConfig, RunConfig
from repro.configs.archs import get_arch
from repro.distributed.steps import make_prefill_step, make_decode_step
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(model_parallel=4)
for name in ["gemma3-1b", "whisper-tiny"]:
    arch = get_arch(name, smoke=True)
    run = RunConfig(mesh_model_parallel=4)
    with jax.set_mesh(mesh):
        pre = make_prefill_step(arch, run, ShapeConfig("p", 32, 4, "prefill"), mesh)
        params = pre.model.init_params(jax.random.PRNGKey(0))
        batch = pre.model.make_inputs(ShapeConfig("p", 32, 4, "prefill"))
        params, batch = pre.place(mesh, params, batch)
        logits, caches = pre.jit()(params, batch)
        assert bool(jnp.all(jnp.isfinite(logits))), name
print("SERVE_OK")
""")
    assert "SERVE_OK" in out


def test_production_mesh_shapes(subproc):
    out = subproc("""
from repro.launch.mesh import make_production_mesh
m1 = make_production_mesh()
assert m1.devices.shape == (16, 16) and m1.axis_names == ("data", "model")
m2 = make_production_mesh(multi_pod=True)
assert m2.devices.shape == (2, 16, 16) and m2.axis_names == ("pod", "data", "model")
print("MESH_OK")
""", devices=512)
    assert "MESH_OK" in out


def test_dryrun_cell_end_to_end(subproc):
    """One full dry-run cell (lower+compile+roofline) inside the 512-device
    world — the integration test for deliverable (e)."""
    out = subproc("""
from repro.launch.dryrun import run_cell
cell = run_cell("llama3.2-1b", "decode_32k", with_probes=True, verbose=False)
assert cell["compile_ok"]
assert cell["roofline"]["t_step_s"] > 0
assert cell["memory"]["peak_gib"] > 0
assert cell["tpu_hbm_estimate"]["fits_hbm_16gib"]
print("CELL_OK", cell["roofline"]["bottleneck"])
""", devices=512)
    assert "CELL_OK" in out


def test_sharded_init_leaves_every_leaf_as_its_spec_says(subproc):
    """Train state (params + AdamW moments) and serving params are made by
    a jit whose outputs carry the bundle's shardings: every leaf arrives
    with its spec's sharding, each device holding only its own shard."""
    out = subproc("""
import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import ShapeConfig, RunConfig
from repro.configs.archs import get_arch
from repro.distributed.steps import (
    init_train_state, make_prefill_step, make_train_step)
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(model_parallel=2)
arch = get_arch("llama3.2-1b", smoke=True)
run = RunConfig(mesh_model_parallel=2)

def check(tree, specs):
    n_split = 0
    leaves = jax.tree.leaves(tree)
    spec_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == len(spec_leaves) > 0
    for x, p in zip(leaves, spec_leaves):
        want = NamedSharding(mesh, p)
        assert x.sharding.is_equivalent_to(want, x.ndim), (x.sharding, p)
        shard = want.shard_shape(x.shape)
        assert all(s.data.shape == shard for s in x.addressable_shards)
        n_split += shard != x.shape
    return n_split

with jax.set_mesh(mesh):
    train = make_train_step(arch, run, ShapeConfig("t", 32, 4, "train"), mesh)
    state = init_train_state(train)
    assert check(state, train.in_shardings[0]) > 0
    pre = make_prefill_step(arch, run, ShapeConfig("p", 32, 4, "prefill"), mesh)
    params = pre.init_params(jax.random.PRNGKey(0))
    assert check(params, pre.in_shardings[0]) > 0
print("SHARDED_INIT_OK")
""", devices=4)
    assert "SHARDED_INIT_OK" in out
