"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before the first
jax initialization.

  single pod : (data=16, model=16)          = 256 chips (one v5e pod)
  multi pod  : (pod=2, data=16, model=16)   = 512 chips

The ``pod`` axis is pure data parallelism across the DCN boundary; ``data``
is intra-pod data parallelism; ``model`` carries TP / expert / sequence /
grid-slab sharding depending on workload.

Every mesh built here has ``AxisType.Auto`` axes: the solver places its
arrays with ``NamedSharding``/``shard_map`` and lets GSPMD propagate
shardings through the unsharded glue code (restrict/prolong, scoring).
``jax.make_mesh`` defaults to ``Explicit`` axes, under which that glue code
raises ``ShardingTypeError``; :func:`auto_axes` converts such a mesh.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """Arbitrary mesh (smoke tests use (1, 1) or (2, 2) host meshes)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis ``AxisType.Auto`` (same devices and names)."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def dp_axis_names(mesh) -> Tuple[str, ...]:
    """The data-parallel axes of a mesh ((pod, data) when pod exists)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def model_axis_name(mesh) -> Optional[str]:
    return "model" if "model" in mesh.axis_names else None


def axis_size(mesh, names) -> int:
    if isinstance(names, str):
        names = (names,)
    out = 1
    for n in names:
        if n in mesh.axis_names:
            out *= mesh.shape[n]
    return out
