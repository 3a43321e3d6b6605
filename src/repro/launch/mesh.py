"""Production mesh builders.

Functions, not module-level constants: importing this module never touches
jax device state, so library imports stay side-effect free (the dry-run sets
XLA_FLAGS before anything else touches jax).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; 2 pods = 512 chips with a leading 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over host (CPU) devices for tests."""
    return jax.make_mesh((data, model), ("data", "model"))

