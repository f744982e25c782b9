"""Production meshes. Functions only — importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init)."""

from __future__ import annotations

import jax

from ..compat import make_mesh

__all__ = ["make_production_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = data * model
    devs = jax.devices()
    assert len(devs) >= n, (len(devs), n)
    return make_mesh((data, model), ("data", "model"), devices=devs[:n])
