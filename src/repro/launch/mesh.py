"""Mesh construction. Functions (not module-level constants) so importing
this module never touches JAX device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: shardings propagate through
    GSPMD from the in/out shardings and ``with_sharding_constraint`` hints
    (jax's default is ``Explicit``, which the model code is not written for)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes), devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """The assignment's production mesh: one v5e pod = 16×16 = 256 chips
    (data × model); multi-pod adds a leading pod axis (2 × 16 × 16 = 512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_tuning_mesh(model_parallel: int, *, chips: int = 256, multi_pod: bool = False):
    """Mesh for a tuner-chosen ``mesh_model_parallel`` factorization of the
    same chip count: data = chips // model (× optional pod axis)."""
    if chips % model_parallel:
        raise ValueError(f"model_parallel {model_parallel} !| chips {chips}")
    data = chips // model_parallel
    if multi_pod:
        return auto_mesh((2, data, model_parallel), ("pod", "data", "model"))
    return auto_mesh((data, model_parallel), ("data", "model"))


def make_host_mesh(model_parallel: int = 1, *, pod: int = 0, devices=None):
    """Mesh over ``devices`` (default: every device this process sees, real
    or fake) — the serving/training drivers' mesh, and the tests'."""
    devices = jax.devices() if devices is None else list(devices)
    n = len(devices)
    if pod:
        data = n // (model_parallel * pod)
        return auto_mesh((pod, data, model_parallel), ("pod", "data", "model"),
                         devices)
    data = n // model_parallel
    return auto_mesh((data, model_parallel), ("data", "model"), devices)
