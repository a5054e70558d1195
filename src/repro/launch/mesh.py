"""Production mesh builders.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; everything else sees the real (single-CPU) device set.

Axes:
  * ``data``  — batch / FSDP weight sharding axis (intra-pod, 16-way);
  * ``model`` — tensor/expert parallel axis (intra-pod, 16-way);
  * ``pod``   — the cross-pod data-parallel axis (2-way on the 512-chip
    2-pod config).  Weights are *replicated* across pods (FSDP gathers stay
    on intra-pod ICI); only the batch and the gradient all-reduce cross the
    pod axis — this matches how real multi-pod v5e jobs are laid out (DCN
    between pods is ~25× slower than ICI).
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh() -> jax.sharding.Mesh:
    """Single-device mesh with the production axis names — lets the same
    pjit'd step functions run on CPU for tests/examples."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def batch_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """Mesh axes that shard the global batch dimension."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def fsdp_axis(mesh: jax.sharding.Mesh) -> str:
    return "data"


def model_axis(mesh: jax.sharding.Mesh) -> str:
    return "model"
