"""Production meshes.

Functions, not module-level constants — importing this module never touches
jax device state. The dry-run process sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any import.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def auto_axes(n: int) -> tuple:
    # jax >= 0.9 makes Explicit axes by default; the sharding rules here
    # constrain activations with_sharding_constraint, which needs Auto axes
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=auto_axes(len(axes)))


def make_mesh_for(n_devices: int, model_parallel: int = 0):
    """Best-effort (data, model) mesh over n_devices (tests, small runs)."""
    if model_parallel <= 0:
        model_parallel = 1
        for cand in (16, 8, 4, 2):
            if n_devices % cand == 0 and n_devices >= cand:
                model_parallel = cand
                break
    return jax.make_mesh((n_devices // model_parallel, model_parallel),
                         ("data", "model"), axis_types=auto_axes(2))


def batch_axes_of(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
