"""Production mesh construction (assignment MULTI-POD DRY-RUN §1).

A function, not a module constant: importing this module never touches jax
device state."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import numpy as np
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — run "
            "under XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "(launch/dryrun.py sets this)")
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over the real local devices (tests/examples)."""
    n = len(jax.devices())
    mp = max(1, min(model_parallel, n))
    return jax.make_mesh((n // mp, mp), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
