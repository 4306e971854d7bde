"""Process set-up shared by the entry points: device check, compile cache.

`launch/serve.py`, `launch/train.py` and `chip_smoke.py` call these before
their first compile.
"""
from __future__ import annotations

import os
import pathlib

import jax

# The checkout that holds this package: <checkout>/src/repro/launch/.
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def require_tpu(allow_cpu_if_requested: bool = True) -> jax.Device:
    """Return JAX's first device, raising unless it is a TPU.

    A run that silently lands on the CPU measures nothing its users pay
    for.  The CPU stays reachable for tests and rehearsals, but only when
    it was asked for explicitly with ``JAX_PLATFORMS=cpu`` (and
    ``allow_cpu_if_requested``).
    """
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        return dev
    if not allow_cpu_if_requested:
        hint = "this program runs only on a TPU"
    elif os.environ.get("JAX_PLATFORMS") == "cpu":
        return dev
    else:
        hint = "set JAX_PLATFORMS=cpu to run on the CPU deliberately"
    raise RuntimeError(
        f"no TPU: JAX's first device is {dev.platform!r} "
        f"({dev.device_kind}); {hint}")


def enable_compile_cache() -> str:
    """Place JAX's persistent compile cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads
    it itself.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a
    fixed path, because the directory is where a later process looks for
    the entries, so a name that changes per run would never be hit.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
