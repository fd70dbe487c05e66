"""Production meshes.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before first init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants (roofline terms, benchmarks, napkin math)
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh helper (elastic re-mesh, tests).

    Axes are ``Auto``: the models place tensors with
    ``with_sharding_constraint`` (``distributed.sharding.constrain``), which
    accepts only Auto axes, while ``jax.make_mesh`` defaults to Explicit."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def chips(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
