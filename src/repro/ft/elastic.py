"""Elastic re-scaling: device loss → largest valid mesh → checkpoint re-shard.

When a pod loses hosts, the surviving device count is refactorized into the
largest usable ``(data, model)`` (or ``(pod, data, model)``) mesh that still
satisfies the model's divisibility needs, and the restored checkpoint is
``device_put`` onto the new mesh's shardings (CheckpointManager.restore does
the placement). Scale-up is the same path in reverse.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax

from repro.configs.base import ArchConfig


def _largest_pow2_le(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def plan_mesh_shape(
    n_devices: int,
    *,
    prefer_model: int = 16,
    arch: Optional[ArchConfig] = None,
    global_batch: Optional[int] = None,
) -> Tuple[int, int]:
    """(data, model) for the largest usable power-of-two device subset.

    Preference order: keep the model axis at ``prefer_model`` (weights keep
    their layout → cheapest re-shard), shrink the data axis; if the surviving
    count is too small, shrink the model axis to the largest power of two
    that still divides the model's sharded dimensions.
    """
    usable = _largest_pow2_le(n_devices)
    model = min(prefer_model, usable)
    if arch is not None:
        # the model axis must divide d_model (densest constraint we use)
        while model > 1 and arch.d_model % model != 0:
            model //= 2
    data = usable // model
    if global_batch is not None:
        while data > 1 and global_batch % data != 0:
            data //= 2
    return data, model


def make_elastic_mesh(n_devices: int, **kw):
    data, model = plan_mesh_shape(n_devices, **kw)
    devices = jax.devices()[: data * model]
    import numpy as np

    return jax.sharding.Mesh(
        np.asarray(devices).reshape(data, model),
        ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )


@dataclass
class ElasticPlan:
    old_devices: int
    new_devices: int
    mesh_shape: Tuple[int, int]

    @property
    def changed(self) -> bool:
        return self.old_devices != self.new_devices
