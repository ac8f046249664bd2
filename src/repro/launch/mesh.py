"""Production mesh construction.

Kept as functions (not module constants) so importing never touches jax
device state; the dry-run sets XLA_FLAGS before any jax import.

Every mesh the repo builds goes through :func:`make_mesh`, which makes
**Auto** axes: model code places activations with
``with_sharding_constraint`` (``pjit_utils.constrain``), which only Auto
axes accept — ``jax.make_mesh`` defaults to Explicit axes.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh

from repro.models.pjit_utils import AxisEnv


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """``jax.make_mesh`` with every axis Auto (see module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_axis_env(mesh: Mesh) -> AxisEnv:
    names = mesh.axis_names
    if "pod" in names:
        return AxisEnv(mesh=mesh, batch_axes=("pod", "data"), model_axis="model")
    return AxisEnv(mesh=mesh, batch_axes=("data",), model_axis="model")


def make_debug_mesh(data: int = 2, model: int = 4) -> Mesh:
    """Small mesh for CI-scale multi-device tests (subprocess-only)."""
    return make_mesh((data, model), ("data", "model"))
