"""Production mesh construction.

Axis semantics (DESIGN.md §6):
  * ``data``  — batch + FSDP (ZeRO-3) axis, ICI within a pod
  * ``model`` — tensor-parallel axis (heads / d_ff / experts / vocab), ICI
  * ``pod``   — multi-pod data axis over DCN; gradient all-reduce crosses
                it once per step (optionally FD top-k compressed)

A FUNCTION, not a module constant — importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).

Mesh construction goes through ``repro.jaxcompat``, which makes every
axis ``AxisType.Auto``.
"""
from __future__ import annotations

import jax

from repro import jaxcompat


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import math
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"{need} devices required (have {len(devices)}); the dry-run "
            "sets XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before any jax import")
    return jaxcompat.make_mesh(shape, axes, devices=devices[:need])


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / examples).

    ``model`` is clamped to the device count (a 1-device CPU host still
    runs every example, just without real model parallelism)."""
    n = len(jax.devices())
    model = max(1, min(model, n))
    data = max(1, n // model)
    return jaxcompat.make_mesh((data, model), ("data", "model"))
