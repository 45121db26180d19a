"""Production mesh builders.

Functions (not module-level constants) so importing never touches jax
device state — the dry-run must set XLA_FLAGS before first jax init.

Target hardware: TPU v5e pods — 256 chips/pod (16×16), 2 pods = 512.
Hardware constants used by the roofline analysis live here too.
"""
from __future__ import annotations

import jax

# TPU v5e per-chip constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BANDWIDTH = 819e9             # B/s
ICI_LINK_BANDWIDTH = 50e9         # B/s per link


def _auto(n_axes: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many real devices exist (CPU tests)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(n // data, 1))
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2))


def make_cache_mesh(model: int | None = None):
    """Mesh for the sharded warm tier of the cache service
    (DESIGN.md §8): every warm shard lives on one `model`-axis device,
    queries stay replicated.  ``model=None`` spans all visible devices;
    otherwise the axis is clamped to the device count (all via
    `make_host_mesh` — one mesh builder, two names).  On CPU CI the
    virtual fleet comes from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    n = len(jax.devices())
    return make_host_mesh(1, n if model is None else max(1, model))
