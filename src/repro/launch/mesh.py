"""Production mesh builders (a FUNCTION, not a module constant: importing
this module never touches jax device state)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """A mesh whose axes are all ``Auto``. The train step and the FL rounds
    place work with sharding constraints and leave the rest to GSPMD; the
    ``Explicit`` axes ``jax.make_mesh`` gives by default refuse both (the
    constraints, and the per-client ``vmap`` over a client-sharded batch)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


# TPU v5e hardware constants for the roofline model (docs/EXPERIMENTS.md §Roofline)
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
