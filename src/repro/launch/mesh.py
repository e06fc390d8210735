"""Production mesh construction (TPU v5e pods; CPU placeholders in dry-run).

A function, not a module constant: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before any jax init).
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_smoke_mesh():
    """Whatever this host offers (1 CPU device in the container)."""
    n = len(jax.devices())
    return _mesh((n, 1), ("data", "model"))


# v5e hardware constants for the roofline (Google Cloud, "TPU v5e")
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
