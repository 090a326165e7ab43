"""Production mesh construction (TPU v5e pod geometry).

Functions, not module-level constants, so importing this module never touches
jax device state (device count locks on first use).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_small_mesh", "make_workers_mesh", "HARDWARE"]

# TPU v5e hardware constants used by the roofline analysis.
HARDWARE = {
    "peak_flops_bf16": 197e12,  # per chip
    "hbm_bandwidth": 819e9,  # bytes/s per chip
    "ici_link_bandwidth": 50e9,  # bytes/s per link
    "ici_links_per_chip": 4,  # 2D torus: 4 links/chip (v5e)
    "hbm_bytes": 16 * 2**30,  # 16 GiB HBM per chip
    "vmem_bytes": 128 * 2**20,
}


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the model's sharding
    constraints and the worker ``shard_map`` are written for Auto axes, and
    ``make_mesh`` defaults to ``Explicit`` ones."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_small_mesh(data: int = 2, model: int = 4):
    """Reduced mesh for CI dry-run tests (8 fake host devices)."""
    return _auto_mesh((data, model), ("data", "model"))


def make_workers_mesh(devices: int | None = None):
    """1-D ``workers`` mesh for the sharded async engine.

    ``devices`` defaults to every local device; the simulated worker count W
    must be a multiple of it (each device shard owns ``W / devices`` worker
    rings/samplers/histograms under ``shard_map``).  On the CI CPU this is a
    1-device mesh — the sharded step then reproduces the single-shard
    trajectory bit-exactly (regression-tested in tests/test_scenarios.py).
    """
    n = jax.local_device_count() if devices is None else devices
    return _auto_mesh((n,), ("workers",))
