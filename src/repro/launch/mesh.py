"""Production mesh construction.

A function (NOT a module-level constant) so importing this module never
touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod: 16x16 = 256 chips ('data' x 'model'); multi-pod adds a
    leading 'pod' axis (2 x 16 x 16 = 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_par: int = 1):
    """Small mesh over the locally available devices (tests/examples)."""
    n = len(jax.devices())
    assert n % model_par == 0
    return _auto_mesh((n // model_par, model_par), ("data", "model"))


def _auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: the sharding rules place arrays
    with ``with_sharding_constraint``, which refuses the ``Explicit`` axes
    that ``jax.make_mesh`` builds by default."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
