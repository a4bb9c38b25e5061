"""The main-path Pallas kernels compile for a TPU v5e chip at real widths,
and the decode step updates its KV cache in place there.

Nothing runs: the TPU compiler that ships with jax compiles for a described,
unattached v5e chip, and refuses what the chip would refuse (block shapes off
the (8, 128) tiling, primitives Mosaic cannot lower, too much VMEM). Interpret
mode checks none of that. Widths: flash attention at llama3.2-1b prefill,
wkv6 at rwkv6-7b, the selective scan at jamba-1.5-large, decode's layers at
InternVL2-26B's. The decode step's memory and copies are the TPU compiler's:
the CPU backend widens a bfloat16 cache to float32 as a whole, which hides
what the program copies.

The topology is described inside a module fixture — never while a module is
imported — so every pytest-xdist worker collects the same tests and only the
worker that runs this file loads the TPU library.
"""
import dataclasses
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.rwkv6.ops import wkv6
from repro.kernels.ssm_scan.ops import selective_scan


@pytest.fixture(scope="module")
def v5e():
    """A described 2x2 v5e host (four chips)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for an unattached chip cannot be read back from the
    # persistent cache; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(v5e):
    return jax.sharding.SingleDeviceSharding(v5e.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


BF16, F32 = jnp.bfloat16, jnp.float32


def test_flash_attention_compiles_at_llama_prefill(one_chip):
    q, kv = (1, 2048, 32, 64), (1, 2048, 8, 64)
    text = _compile(lambda q, k, v: flash_attention(q, k, v, causal=True),
                    one_chip, (q, BF16), (kv, BF16), (kv, BF16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [BF16, F32])
def test_wkv6_compiles_at_rwkv6_7b(one_chip, dtype):
    x = (1, 512, 64, 64)
    text = _compile(wkv6, one_chip, (x, dtype), (x, dtype), (x, dtype),
                    (x, F32), ((64, 64), F32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [BF16, F32])
def test_selective_scan_compiles_at_jamba(one_chip, dtype):
    x, bc = (1, 512, 16384), (1, 512, 16)
    text = _compile(selective_scan, one_chip, (x, dtype), (x, dtype),
                    (bc, dtype), (bc, dtype), ((16384, 16), F32))
    assert "tpu_custom_call" in text


def _compile_decode(devices, width: dict, run, positions: int):
    """``make_decode_step`` for an InternVL2-26B-shaped GQA model of the
    given widths, batch 8, compiled over ``devices``: (bundle, compiled)."""
    from repro.configs.archs import get_arch
    from repro.configs.base import ShapeConfig
    from repro.distributed.steps import make_decode_step
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(len(devices), devices=list(devices))
    arch = dataclasses.replace(get_arch("internvl2-26b", smoke=True),
                               head_dim=128, vocab_size=4096, **width)
    with jax.set_mesh(mesh):
        step = make_decode_step(arch, run, ShapeConfig("d", positions, 8, "decode"), mesh)
        shardings = jax.tree.map(lambda p: NamedSharding(mesh, p), step.in_shardings,
                                 is_leaf=lambda x: isinstance(x, PartitionSpec))
        args = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                            step.abstract_inputs, shardings)
        return step, step.jit().lower(*args).compile()


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_decode_step_writes_the_donated_cache_in_place(one_chip, kv_dtype):
    """A GQA decode step (8 KV heads of 128, batch 8, 2048 positions, 4
    layers) with its caches donated: the output cache is the input's buffer,
    and the step's temporaries hold less than one layer stack of K. A step
    that rebuilt the stacks as the layer scan's outputs needs two."""
    from repro.configs.base import RunConfig

    step, compiled = _compile_decode(
        one_chip.device_set, dict(num_layers=4, d_model=1024, num_heads=16,
                                  num_kv_heads=8, d_ff=2048),
        RunConfig(kv_cache_dtype=kv_dtype), 2048)
    memory = compiled.memory_analysis()
    caches = step.abstract_inputs[1]
    k_stack = caches["l0"]["k"].size * caches["l0"]["k"].dtype.itemsize
    cache_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(caches))
    assert step.donate_argnums == (1,)
    assert memory.alias_size_in_bytes == cache_bytes
    assert memory.temp_size_in_bytes < k_stack, (memory.temp_size_in_bytes, k_stack)


@pytest.mark.parametrize("mp", [1, 4])
def test_decode_reads_qkv_weights_in_place(v5e, mp):
    """Two decode layers at InternVL2-26B's widths (d 6144, 48/8 heads of
    128, d_ff 16384), batch 8, 3072 cache positions, on one chip and
    tensor-parallel over four: no layer's weight is copied out of its stack
    (a ``[1, d, *]`` copy), and the step's temporaries hold less than one
    layer's wk a chip, the smallest weight a projection reads. A projection
    whose output layout RoPE steers reads a transposed copy of wq, wk and wv
    every layer (temporaries 76 MB at mp=1, 3.9 MB a chip at mp=4)."""
    from repro.configs.base import RunConfig

    _, compiled = _compile_decode(
        v5e.devices[:mp], dict(num_layers=2, d_model=6144, num_heads=48,
                               num_kv_heads=8, d_ff=16384),
        RunConfig(mesh_model_parallel=mp), 3072)
    copies = re.findall(r"= \w+\[1,6144,\d+\]\S* copy\(", compiled.as_text())
    wk_bytes = 6144 * 8 * 128 * 2 // mp
    assert not copies, copies
    assert compiled.memory_analysis().temp_size_in_bytes < wk_bytes
