"""No fallback that hides the device: the entry points' device check, the
compile-cache placement, the per-device VMEM budget, and the backend
resolution count the chip smoke run audits."""
import os
import pathlib
import subprocess
import sys
import types

import jax
import pytest

from repro.analysis import vmem
from repro.kernels import substrate
from repro.launch import runtime

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_require_tpu_allows_cpu_only_when_asked(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert runtime.require_tpu().platform == "cpu"
    with pytest.raises(RuntimeError, match="'cpu'"):
        runtime.require_tpu(allow_cpu_if_requested=False)
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="no TPU"):
        runtime.require_tpu()


def test_compile_cache_follows_env_else_checkout(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert runtime.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = runtime.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def _fake_devices(platform, kind):
    return lambda: [types.SimpleNamespace(platform=platform,
                                          device_kind=kind)]


def test_vmem_budget_keyed_by_device_kind(monkeypatch):
    monkeypatch.delenv("REPRO_VMEM_BUDGET_BYTES", raising=False)
    monkeypatch.setattr(vmem.jax, "devices",
                        _fake_devices("tpu", "TPU v5 lite"))
    assert vmem.vmem_budget_bytes() == 16 * 1024 * 1024
    monkeypatch.setattr(vmem.jax, "devices",
                        _fake_devices("tpu", "TPU v99"))
    with pytest.raises(ValueError, match="TPU v99"):
        vmem.vmem_budget_bytes()
    monkeypatch.setenv("REPRO_VMEM_BUDGET_BYTES", "4096")
    assert vmem.vmem_budget_bytes() == 4096


def test_resolve_backend_is_counted():
    before = dict(substrate.resolved)
    with substrate.force_backend("ref"):
        substrate.resolve_backend(None)
    substrate.resolve_backend(True)
    assert substrate.resolved["ref"] == before.get("ref", 0) + 1
    assert substrate.resolved["interpret"] == before.get("interpret", 0) + 1


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], env=env,
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "'cpu'" in out.stderr
    assert '"ok"' not in out.stdout
