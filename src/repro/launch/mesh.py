"""Production meshes.

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis carries pure data parallelism across pods (batch shards over
pod x data), so cross-pod traffic is gradient all-reduce only.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax


def _mesh_kwargs(n_axes: int) -> dict:
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_mesh_kwargs(len(axes)))


def make_host_mesh(model_par: int = 1):
    """Mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    data = n // model_par
    return jax.make_mesh((data, model_par), ("data", "model"),
                         **_mesh_kwargs(2))
