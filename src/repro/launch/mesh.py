"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before its first
jax import, and everything else must see the real single CPU device.

Every mesh is built with ``Auto`` axis types: the model code places
activations with ``with_sharding_constraint`` and lets the partitioner
propagate the rest, which explicit-axis meshes (``jax.make_mesh``'s
default) refuse.
"""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod mesh: (data=16, model=16) = 256 chips; multi-pod adds a
    leading pure-DP pod axis (2 pods = 512 chips over DCI)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_parallel: int = 1, *, devices=None):
    """(data, model) mesh over ``devices`` (default: every local device)."""
    devices = jax.devices() if devices is None else list(devices)
    n = len(devices)
    assert n % model_parallel == 0
    return make_mesh((n // model_parallel, model_parallel),
                     ("data", "model"), devices=devices)


def mesh_axis_sizes(mesh) -> dict:
    return dict(mesh.shape)   # works for Mesh and AbstractMesh


def data_parallel_size(mesh) -> int:
    sizes = mesh_axis_sizes(mesh)
    return math.prod(sizes.get(a, 1) for a in ("pod", "data"))
