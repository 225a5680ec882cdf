"""Production mesh construction.

Single pod: v5e 16x16 = 256 chips, axes ("data", "model").
Multi-pod:  2 pods x 256 = 512 chips, axes ("pod", "data", "model") —
the "pod" axis carries cross-pod data parallelism (+ optional int8
gradient compression, repro.optim.compress).

Defined as functions (never module-level constants) so importing this
module touches no jax device state.
"""

from __future__ import annotations

import jax


def get_abstract_mesh():
    """Ambient abstract mesh (None when unset)."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Degenerate mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    return _make_mesh((n // model, model), ("data", "model"))
