"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Shapes:
  single-pod : (16, 16)    axes ("data", "model")   — 256 chips
  multi-pod  : (2, 16, 16) axes ("pod", "data", "model") — 512 chips
The ``pod`` axis composes with ``data`` for batch DP (gradient all-reduce
crosses pods over DCN); ``model`` carries TP/EP within a pod's ICI domain.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests use small ones, e.g. (2, 2)).  Axes are
    ``Auto``: the sharding rules and ``shard_map`` bodies of this repo
    leave propagation to the compiler, which ``jax.make_mesh``'s
    ``Explicit`` default (JAX >= 0.7) rejects."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # B/s per chip
ICI_BW = 50e9                   # B/s per link
CHIPS = {"single": 256, "multi": 512}
