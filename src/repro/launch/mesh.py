"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import to obtain placeholder devices.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None):
    """Every mesh in the repo is built here, with Auto axes: the model code
    shards through ``with_sharding_constraint`` and leaves the rest to the
    partitioner, which Explicit axes (``jax.make_mesh``'s default) refuse."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256-chip single pod, or 2x16x16 = 512-chip two-pod mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh for CPU integration tests (requires forced host devices)."""
    return make_mesh((data, model), ("data", "model"))


# TPU v5e-class hardware constants for the roofline analysis.
HW = {
    "peak_flops": 197e12,      # bf16 FLOP/s per chip
    "hbm_bw": 819e9,           # bytes/s per chip
    "ici_bw": 50e9,            # bytes/s per link (~per chip aggregate used)
    "hbm_bytes": 16e9,         # HBM capacity per chip
}
