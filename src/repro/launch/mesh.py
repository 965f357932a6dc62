"""Production meshes.

Functions, not module constants — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS *before* any jax init; the
trainer uses whatever devices exist).
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh", "HW"]


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods for the multi-pod config."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the sharding rules are with_sharding_constraint hints
    # that GSPMD propagates, which Explicit axes (the default) reject
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_local_mesh(*, dp: int = 0, tp: int = 1, devices=None):
    """Local ``(data, model)`` mesh over this process's devices.

    ``tp`` sizes the "model" (tensor-parallel) axis; ``dp`` sizes the
    "data" axis, with ``dp=0`` meaning "all remaining devices"
    (``len(devices) // tp``).  ``devices`` restricts the mesh to an
    explicit device list (the serve router hands each engine replica a
    disjoint slice); default is every device jax sees.  Divisibility is
    validated up front — GSPMD would reject an uneven mesh anyway, but
    the error here names the sizes.  The no-argument call keeps the old
    behaviour: an ``(n, 1)`` data-only mesh.
    """
    import numpy as np

    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    n = len(devices)
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if dp < 0:
        raise ValueError(f"dp must be >= 0 (0 = all remaining devices), "
                         f"got {dp}")
    if dp == 0:
        if n % tp:
            raise ValueError(f"tp={tp} does not divide the {n} available "
                             f"device(s)")
        dp = n // tp
    if dp * tp > n:
        raise ValueError(f"mesh ({dp}, {tp}) needs {dp * tp} devices but "
                         f"only {n} are available")
    grid = np.array(devices[:dp * tp], dtype=object).reshape(dp, tp)
    from jax.sharding import Mesh
    return Mesh(grid, ("data", "model"))


class HW:
    """TPU v5e hardware constants used by the roofline analysis."""
    PEAK_BF16_FLOPS = 197e12        # per chip
    HBM_BW = 819e9                  # bytes/s per chip
    ICI_BW = 50e9                   # bytes/s per link (~per-direction)
    HBM_BYTES = 16 * 2 ** 30        # 16 GiB per chip
