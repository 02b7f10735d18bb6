"""The host mesh of the sharded machine phase (DESIGN.md §7).

Defined as a FUNCTION (not a module-level constant) so importing this
module never touches jax device state — a caller that forces host devices
must set XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax

from jax.sharding import AxisType


def make_host_mesh(data: int = 1, model: int = 1):
    """(data, model) mesh over the first ``data * model`` devices."""
    ndev = data * model
    return jax.make_mesh((data, model), ("data", "model"),
                         devices=jax.devices()[:ndev],
                         axis_types=(AxisType.Auto,) * 2)
