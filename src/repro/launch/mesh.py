"""Production mesh construction (deliverable (e)).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run must set XLA_FLAGS first).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes: the sharding policies constrain
    with bare ``PartitionSpec``s, which ``Explicit`` axes (the default) reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; 2 pods = 512 chips with a leading 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever fits the local devices — used by smoke tests/examples."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))


# TPU v5e-class hardware constants for the roofline analysis (§Roofline).
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link (1 effective link/chip assumed; see docs)
HBM_PER_CHIP = 16 * 2**30  # 16 GiB
