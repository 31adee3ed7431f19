"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count before first jax init.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e-256 pod: (data=16, model=16); two pods add a leading
    'pod' axis (data-parallel across the DCN/ICI-linked pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    """Axes usable for batch/data parallelism on this mesh."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def axis_size(mesh, name: str) -> int:
    if name not in mesh.axis_names:
        return 1
    return mesh.shape[name]
