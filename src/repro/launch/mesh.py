"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (the dry-run needs to set XLA_FLAGS before any jax
initialization).

Topology (TPU v5e-class):
  single-pod: (data=16, model=16)          = 256 chips
  multi-pod:  (pod=2, data=16, model=16)   = 512 chips

The "model" axis carries TP/EP (high-bandwidth inner axis), "data" carries
DP/FSDP-style weight sharding and sequence sharding for long-context cells,
and "pod" is pure DP across pods (lowest-bandwidth links: DCN).
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P  # noqa: F401  (re-exported)


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for host-device tests (XLA_FLAGS device-count 8)."""
    return _mesh(shape, axes)


def dp_axes(mesh) -> tuple:
    """The data-parallel axes for this mesh ('pod' composes with 'data')."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1
