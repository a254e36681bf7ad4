"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS *before* any jax
initialization and only then calls ``make_production_mesh``.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 two-pod (512 chips).

    Axis roles: "pod" — slow inter-pod link, pure DP (+ optional compressed
    grad sync / pipeline stages); "data" — DP + FSDP storage sharding;
    "model" — TP / EP / SP.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for CPU multi-device tests (requires forced device count)."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types: the sharding rules in
    ``repro.sharding`` place arrays with ``NamedSharding`` constraints and
    let the partitioner propagate the rest, which Explicit axes refuse."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
