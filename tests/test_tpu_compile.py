"""The main-path Pallas kernels compile for a TPU v5e chip at real widths.

Nothing runs: the TPU compiler that ships with jax compiles for a described,
unattached v5e chip, and refuses what the chip would refuse (block shapes off
the (8, 128) tiling, primitives Mosaic cannot lower, too much VMEM). Interpret
mode checks none of that. Widths: flash attention at llama3.2-1b prefill,
wkv6 at rwkv6-7b, the selective scan at jamba-1.5-large.

The topology is described inside a module fixture — never while a module is
imported — so every pytest-xdist worker collects the same tests and only the
worker that runs this file loads the TPU library.
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.rwkv6.ops import wkv6
from repro.kernels.ssm_scan.ops import selective_scan


@pytest.fixture(scope="module")
def one_chip():
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
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


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
