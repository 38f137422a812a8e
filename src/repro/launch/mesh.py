"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run must set XLA_FLAGS
before the first jax initialization.
"""
from __future__ import annotations

from jax.sharding import Mesh

from repro.distributed.sharding import auto_mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Single-device mesh for CPU smoke tests (1×1)."""
    return auto_mesh((1, 1), ("data", "model"))


def mesh_axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
