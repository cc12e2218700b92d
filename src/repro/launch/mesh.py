"""Production mesh builders. Functions, not module constants, so importing
this module never touches jax device state."""
from __future__ import annotations

import jax

# ``constrain`` places activations with ``with_sharding_constraint``, which
# takes Auto mesh axes only; ``jax.make_mesh`` makes Explicit ones by default
_AUTO = jax.sharding.AxisType.Auto


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(_AUTO,) * len(axes))


def make_host_mesh():
    """Single-device mesh for CPU smoke runs."""
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=(_AUTO,) * 2)
