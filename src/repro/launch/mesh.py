"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then builds meshes.
"""

from __future__ import annotations

import jax

__all__ = ["make_local_mesh", "make_production_mesh"]


def _auto(n_axes: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16×16 = 256 chips (data, model).
    Multi-pod: 2×16×16 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_local_mesh(model: int = 1):
    """Whatever this process has (tests/examples: 1 CPU device)."""
    n = jax.device_count()
    return jax.make_mesh(
        (n // model, model), ("data", "model"), axis_types=_auto(2))


# TPU v5e hardware constants (per chip) for the roofline terms
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW_PER_LINK = 50e9            # bytes/s per link (~)
HBM_PER_CHIP = 16 * 1024**3      # bytes
