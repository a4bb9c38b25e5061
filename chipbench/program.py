"""How the engines reach the program: its registered architecture at the
configuration's sizes, parameter shardings, and per-unit counts."""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.archs import get_arch

# configuration key -> the program's ArchConfig attribute
ARCH_KEYS = {
    "hidden_size": "d_model", "num_hidden_layers": "num_layers",
    "decoder_layers": "num_layers", "encoder_layers": "encoder_layers",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "resolved_head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings", "max_source_positions": "frontend_seq",
}


def footprint_bytes(bundle) -> int:
    """Device bytes one compiled step program holds while it runs: its
    arguments, outputs not aliased to them, and temporaries (which the
    runtime's ``peak_bytes_in_use`` leaves out). The program comes from the
    compile cache the window's call filled (the same jit as
    ``StepBundle.jit``)."""
    jitted = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                     out_shardings=bundle.out_shardings,
                     donate_argnums=bundle.donate_argnums)
    m = jitted.lower(*bundle.abstract_inputs).compile().memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes)


def arch(cfg: dict, image_tokens: int | None = None):
    """The registry's architecture at the configuration's depth
    (``num_hidden_layers``, the one cut a configuration may make), refused if
    any other size the configuration states differs from it. A vision
    model's image-token count is the traffic's (256 per image tile), so the
    stub frontend takes ``image_tokens`` positions."""
    a = get_arch(cfg["arch"], smoke=cfg.get("smoke", False))
    if "num_hidden_layers" in cfg:
        a = dataclasses.replace(a, num_layers=cfg["num_hidden_layers"])
    if image_tokens is not None:
        a = dataclasses.replace(a, frontend_seq=image_tokens)
    for key, attr in ARCH_KEYS.items():
        if key in cfg and getattr(a, attr) != cfg[key]:
            raise ValueError(f"{cfg['name']}: {key} = {cfg[key]} but the program's "
                             f"{cfg['arch']} has {attr} = {getattr(a, attr)}")
    return a


def named(mesh, specs):
    """A PartitionSpec tree as NamedShardings on ``mesh``."""
    return jax.tree.map(lambda p: NamedSharding(mesh, p), specs,
                        is_leaf=lambda x: isinstance(x, PartitionSpec))


def sum_counts(unit_counts: list, units) -> dict:
    """The analytic counts of the given units, summed by key."""
    out: dict = {}
    for u in units:
        for k, v in unit_counts[u].items():
            out[k] = out.get(k, 0) + v
    return out
