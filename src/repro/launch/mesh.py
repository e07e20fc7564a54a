"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax init; tests and benches
see the default single device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests use small virtual-device meshes), Auto axes:
    the sharding rules place data and leave propagation to the compiler
    (see distributed.sharding.auto_axes)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


# TPU v5e hardware model used by the roofline analysis.
HW = {
    "peak_flops_bf16": 197e12,  # per chip
    "hbm_bw": 819e9,  # bytes/s per chip
    "ici_bw": 50e9,  # bytes/s per link (~per axis direction)
    "hbm_bytes": 16 * 1024**3,
    "vmem_bytes": 16 * 1024**2,
}
