"""Production meshes (DESIGN.md §3) + elastic re-meshing.

Functions, not module constants — importing this module never touches jax
device state. The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any import.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

from repro.core.dist_bfs import make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def remesh(failed_devices: set, *, axes=("data", "model")) -> Mesh:
    """Elastic restart: rebuild the largest rectangular mesh from survivors.

    Drops whole rows of the device grid containing failed devices (the
    standard slice-granularity recovery on TPU pods), returns a smaller mesh;
    checkpoint.reshard() then maps the last checkpoint onto it.
    """
    devices = [d for d in jax.devices() if d.id not in failed_devices]
    n = len(devices)
    model = min(16, n)
    while n % model:
        model -= 1
    data = n // model
    grid = np.array(devices[: data * model]).reshape(data, model)
    return Mesh(grid, axes)
