"""Production mesh factories.

A function (not a module-level constant) so importing this module never
touches jax device state — device count is locked at first jax init, and
only dryrun.py forces the 512-host-device XLA flag.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """v5e-256 pod mesh: (data=16, model=16); multi-pod adds pod=2 (DCN)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over the host's actual devices (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(n // data, 1))
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
