"""Production mesh factories.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init, and nothing here may run earlier.
"""
from __future__ import annotations

import jax

from repro.dist.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e target: 16x16 (256 chips) per pod; 2 pods over DCN."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1, pods: int = 1):
    """Small mesh over whatever devices this host actually has (examples)."""
    n = len(jax.devices())
    mp = max(g for g in range(1, model_parallel + 1) if n % g == 0)
    rest = n // mp
    if pods > 1 and rest % pods == 0:
        return make_mesh((pods, rest // pods, mp), ("pod", "data", "model"))
    return make_mesh((rest, mp), ("data", "model"))
