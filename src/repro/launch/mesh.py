"""Production meshes (TPU v5e pods).

Never touches jax device state at import time — meshes are built inside
functions only (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips).

    Axes: ``pod`` — pure data parallel across pods (DCN);
    ``data`` — FSDP + batch; ``model`` — TP / SP / seq-sharded KV.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices (got {len(devices)}); the dry-run entrypoint "
            "must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before importing jax")
    import numpy as np
    return jax.sharding.Mesh(
        np.asarray(devices).reshape(shape), axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (unit tests)."""
    import numpy as np
    n = data * model
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    return jax.sharding.Mesh(
        np.asarray(devices).reshape(data, model), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
