"""Production mesh construction.

Defined as FUNCTIONS (no module-level device access) so importing this
module never initialises jax device state: a caller that wants a forced
host device count (``XLA_FLAGS=--xla_force_host_platform_device_count=N``)
sets it before any import, and smoke tests see the real single device.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256-chip pod ("data", "model"); multi_pod adds a leading
    2-wide "pod" axis (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_smoke_mesh(devices: int | None = None) -> jax.sharding.Mesh:
    """Degenerate mesh over whatever devices exist (CPU tests)."""
    n = devices or len(jax.devices())
    return jax.make_mesh((1, n), ("data", "model"))


def dp_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """Data-parallel axes: ("pod", "data") when the pod axis exists."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def axis_size(mesh: jax.sharding.Mesh, *names: str) -> int:
    out = 1
    for n in names:
        if n in mesh.axis_names:
            out *= mesh.shape[n]
    return out
