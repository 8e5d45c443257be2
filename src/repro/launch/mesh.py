"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state; the dry-run sets
XLA_FLAGS before any jax import to fabricate 512 host devices.

Single pod: 16 × 16 = 256 chips, axes (data, model).
Multi-pod:  2 × 16 × 16 = 512 chips, axes (pod, data, model) — "pod"
composes with "data" for batch sharding and gradient reduction (DCN-level
all-reduce), proving the distribution config scales past one ICI domain.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh", "HW"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """1-D ``("data",)`` mesh over every local device: one data-parallel rank
    per chip.  Axes are Auto, so a jitted step given batch-sharded inputs and
    replicated parameters gets its gradient all-reduce from the compiler."""
    return jax.make_mesh(
        (jax.device_count(),), ("data",), axis_types=(AxisType.Auto,)
    )


class HW:
    """TPU v5e-class hardware constants (roofline denominators)."""

    PEAK_FLOPS_BF16 = 197e12  # per chip
    HBM_BW = 819e9  # bytes/s per chip
    ICI_BW = 50e9  # bytes/s per link
    HBM_BYTES = 16e9  # per chip
