"""Mesh construction.

Every mesh in the repo comes from :func:`make_mesh`, which gives each
axis ``AxisType.Auto``: GSPMD propagates shardings through the program,
as ``shard_map`` regions and the ``train_shardings`` rules expect.
``jax.make_mesh`` alone makes ``Explicit`` axes on the installed JAX.

Single pod: (16, 16) = ("data", "model") — 256 v5e chips.
Multi-pod:  (2, 16, 16) = ("pod", "data", "model") — 512 chips; the "pod"
axis crosses the slower inter-pod links, so DP spans ("pod","data") and
the hierarchical collectives in repro.core.hierarchical split legs
accordingly.

FUNCTIONS, not module-level constants: importing this module never
touches jax device state (dryrun.py must set XLA_FLAGS first).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "HW"]


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with every axis ``Auto``; ``devices`` defaults to
    all of ``jax.devices()``."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


class HW:
    """TPU v5e per-chip roofline constants (Google Cloud, "TPU v5e")."""

    PEAK_FLOPS_BF16 = 197e12  # FLOP/s
    HBM_BW = 819e9  # B/s
    ICI_BW_PER_LINK = 50e9  # B/s/link (~)
    HBM_BYTES = 16 * 1024**3
