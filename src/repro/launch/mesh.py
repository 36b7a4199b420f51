"""Production meshes.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benchmarks see the real single CPU device.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests, elastic re-mesh, single-device smoke)."""
    return _make_mesh(tuple(shape), tuple(axes))


def make_host_mesh():
    """1-device mesh with production axis names (CPU tests)."""
    return make_mesh((1, 1), ("data", "model"))


# TPU v5e hardware model used for the roofline (per chip).
HW = {
    "peak_bf16_flops": 197e12,      # FLOP/s
    "hbm_bw": 819e9,                # B/s
    "ici_bw": 50e9,                 # B/s per link
    "hbm_bytes": 16e9,
}
