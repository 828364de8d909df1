"""Production mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device
state.  The dry-run sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512``
before any jax import; everything else sees the real device count.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes are all Auto: the sharding code annotates with
    ``with_sharding_constraint``, which only Auto axes accept (jax.make_mesh
    defaults to Explicit axes)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (TPU v5e pod); 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh():
    """Whatever this host has (CPU smoke tests: 1 device)."""
    n = len(jax.devices())
    return _auto_mesh((1, n), ("data", "model"))


# TPU v5e hardware constants (roofline; DESIGN.md §2)
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9            # bytes/s per chip
ICI_BW = 50e9             # bytes/s per link
