"""Production mesh construction.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — required because the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init,
while tests and benches must see the single real device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = ("data", "model") — 256 chips (TPU v5e pod).
    Multi-pod: (2, 16, 16) = ("pod", "data", "model") — 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, *, pod: int = 0):
    """Small virtual mesh for CPU integration tests
    (requires xla_force_host_platform_device_count >= n_data*n_model*pod)."""
    if pod:
        return _auto_mesh((pod, n_data, n_model), ("pod", "data", "model"))
    return _auto_mesh((n_data, n_model), ("data", "model"))


def _auto_mesh(shape, axes):
    """A mesh whose axes are all Auto: the model code places arrays with
    `with_sharding_constraint` and lets XLA propagate the rest, which
    Explicit axes (the `jax.make_mesh` default) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


# TPU v5e hardware constants used by the roofline analysis (per chip).
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link
