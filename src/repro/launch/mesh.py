"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
device query.
"""

from __future__ import annotations

import contextlib

import jax

from repro.models.common import use_abstract_mesh


@contextlib.contextmanager
def mesh_context(mesh):
    """Enter BOTH the physical and abstract mesh contexts.

    ``get_abstract_mesh()`` inside jit tracing only sees the mesh under
    ``use_abstract_mesh`` — model code (MoE shard_map, constraint helpers)
    relies on it."""
    with mesh, use_abstract_mesh(mesh.abstract_mesh):
        yield mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Degenerate mesh over however many real devices exist (tests/smoke)."""
    n = len(jax.devices())
    data = max(n // model_axis, 1)
    return jax.make_mesh((data, model_axis), ("data", "model"))


def mesh_info(mesh) -> dict:
    return {
        "axes": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "n_devices": int(mesh.devices.size),
    }
