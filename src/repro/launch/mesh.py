"""Production mesh construction (TPU v5e pods).

A FUNCTION, not a module-level constant — importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _axis_kw(n):
    return {"axis_types": (AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16)=256 chips per pod; (2,16,16)=512 chips across 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kw(len(shape)))


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 1):
    """Small mesh over whatever devices exist (CPU smoke tests)."""
    shape, axes = [], []
    for n, a in ((pod, "pod"), (data, "data"), (model, "model")):
        if n > 1 or a != "pod":
            shape.append(n)
            axes.append(a)
    return jax.make_mesh(tuple(shape), tuple(axes),
                         **_axis_kw(len(shape)))


# Hardware constants (TPU v5e, per chip) — used by the roofline report.
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s per link
CHIPS_PER_POD = 256
