"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state. The dry-run entry point sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import so these meshes can be built from host placeholder devices.

Target hardware: TPU v5e, 256 chips/pod (16×16), 2 pods.
  peak 197 TFLOP/s bf16/chip · 819 GB/s HBM/chip · ~50 GB/s/link ICI.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# v5e hardware constants used by the roofline (see EXPERIMENTS.md §Roofline)
PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh over however many devices exist (tests)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
