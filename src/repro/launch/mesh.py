"""Production meshes (as functions — importing this never touches jax
device state; jax locks the device count on first backend init).

Mesh shapes:
    single-pod: (data=16, model=16)            = 256 chips (one v5e pod)
    multi-pod:  (pod=2, data=16, model=16)     = 512 chips (dry-run target)

The `pod` axis is pure data parallelism whose all-reduce crosses the
inter-pod link (DCN on a real fleet) — gradients cross it int8-compressed
(repro/train/compression.py). Scaling to 1000+ nodes grows `pod`; nothing
else in the rule set changes (DESIGN §4).
"""
from __future__ import annotations

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple, axes: tuple):
    """Mesh over the first prod(shape) devices (placeholder CPU devices in
    the dry-run; real TPU topology on a fleet)."""
    import jax
    from jax.sharding import AxisType
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {dict(zip(axes, shape))} needs {n} devices, have "
            f"{len(devices)} — the dry-run entry point must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import")
    # Auto axes: every sharding in the repo is steered by GSPMD
    # (`with_sharding_constraint`, jit in/out shardings), which
    # `jax.make_mesh`'s default Explicit axes reject.
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(axes: tuple = ("data", "model")):
    """1-device mesh for CPU tests/examples: every rule resolves to no-op."""
    return make_mesh((1,) * len(axes), axes)


def pods_in(mesh) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get("pod", 1)
