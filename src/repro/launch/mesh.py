"""Production mesh factory.

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
leading "pod" axis is pure data parallelism (DCN-connected pods).

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state; `elastic_mesh` builds arbitrary healthy-subset
meshes for the fault-tolerance path, and `best_effort_mesh` factors
whatever device count the platform actually exposes (the sweep driver's
entry point under `--xla_force_host_platform_device_count`).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def _mk(shape, axes):
    n_have = len(jax.devices())
    n_need = math.prod(shape)
    if n_need != n_have:
        raise ValueError(
            f"mesh {dict(zip(axes, shape))} needs {n_need} devices but "
            f"the platform exposes {n_have}; pick axis sizes whose "
            f"product is {n_have} (elastic_mesh / best_effort_mesh) or "
            f"launch with more devices "
            f"(--xla_force_host_platform_device_count on CPU)")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def elastic_mesh(pods: int, data: int, model: int):
    """Mesh for an elastic restart on a reduced healthy set."""
    if min(pods, data, model) < 1:
        raise ValueError(
            f"mesh axis sizes must be >= 1, got pods={pods} data={data} "
            f"model={model}")
    if pods > 1:
        return _mk((pods, data, model), ("pod", "data", "model"))
    return _mk((data, model), ("data", "model"))


def best_effort_mesh(n_devices=None, *, prefer: str = "model"):
    """("data", "model") mesh over the first `n_devices` available.

    Factors n into data x model, putting as much of it as possible on
    the preferred axis (all of it when n is prime).  The sweep driver
    uses this so one worker binary serves any
    --xla_force_host_platform_device_count.
    """
    if prefer not in ("data", "model"):
        raise ValueError(f"prefer must be 'data' or 'model': {prefer!r}")
    n_have = len(jax.devices())
    n = n_have if n_devices is None else int(n_devices)
    if not 1 <= n <= n_have:
        raise ValueError(
            f"best_effort_mesh(n_devices={n_devices}): platform exposes "
            f"{n_have} devices")
    shape = (1, n) if prefer == "model" else (n, 1)
    devs = jax.devices()[:n]
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.asarray(devs).reshape(shape), ("data", "model"))


def smoke_mesh():
    """1-device mesh with production axis names (CPU tests)."""
    return _mk((1, 1), ("data", "model"))
