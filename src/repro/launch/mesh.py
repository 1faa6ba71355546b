"""Production meshes for the dry-run target (TPU v5e-class pods).

A function, not a module-level constant: importing this module must never
touch jax device state (smoke tests see 1 CPU device; only dryrun.py forces
512 placeholder devices before its first jax import).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

#: hardware constants (v5e-class chip) used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12        # FLOP/s per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
HBM_BYTES = 16 * 2 ** 30        # per chip


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(*, multi_pod: bool = False):
    """Reduced mesh for CI (8 placeholder devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))
