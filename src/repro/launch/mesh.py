"""Production meshes.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state — the dry-run must set
``XLA_FLAGS`` *before* the first jax initialization.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.parallel.mesh_ctx import MeshCtx


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_ctx(mesh, *, fsdp_over_pod: bool = False, **knobs) -> MeshCtx:
    """MeshCtx with batch/FSDP axes derived from the mesh's axis names."""
    names = tuple(mesh.axis_names)
    batch = tuple(a for a in names if a in ("pod", "data"))
    fsdp = batch if (fsdp_over_pod and "pod" in names) else ("data",)
    return MeshCtx(mesh, batch_axes=batch, fsdp_axes=fsdp, **knobs)


def make_smoke_mesh(n_data: int = 2, n_model: int = 2):
    """Tiny mesh for CPU tests (requires host-device override ≥ n_data·n_model)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
