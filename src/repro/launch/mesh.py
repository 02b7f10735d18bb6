"""Production mesh builders.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
jax initialization.
"""
from __future__ import annotations

import math

import jax

from jax.sharding import AxisType


def _axis_types_kw(n_axes: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; the multi-pod mesh adds a leading DCN 'pod'
    axis (2 pods = 512 chips).  Scaling to 1000+ nodes grows only the 'pod'
    extent — in-pod layouts are untouched."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    ndev = math.prod(shape)
    devices = jax.devices()
    if len(devices) < ndev:
        raise RuntimeError(
            f"mesh {shape} needs {ndev} devices but only {len(devices)} "
            "are visible — the dry-run launcher must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import")
    return jax.make_mesh(shape, axes, devices=devices[:ndev],
                         **_axis_types_kw(len(axes)))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    ndev = data * model
    return jax.make_mesh((data, model), ("data", "model"),
                         devices=jax.devices()[:ndev],
                         **_axis_types_kw(2))
