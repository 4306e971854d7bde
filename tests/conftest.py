"""Shared test config: make `hypothesis` optional WITHOUT losing coverage.

Several modules do a hard `from hypothesis import given, settings,
strategies as st` at the top.  With the real package installed (it is in
requirements.txt; CI installs it) nothing here runs.  On minimal
environments without the wheel we install `tests/_minihypothesis.py` into
`sys.modules` BEFORE the test modules import it — a tiny functional
stand-in that actually EXECUTES each property test over deterministic
pseudo-random examples, so the property suite passes with real coverage
instead of skipping (the pre-PR-2 shim replaced every @given test with a
skip).
"""
import importlib.util
import os
import sys

# The suite runs on the CPU: kernels in interpret mode or on their jnp
# refs.  tests/test_tpu_compile.py compiles for a described TPU without
# attaching one.  An explicit JAX_PLATFORMS wins.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Expose 8 host devices BEFORE anything imports jax, so the sharded
# parity suite (tests/test_sharded_parity.py) runs in-process on real
# shard_map meshes.  Harmless for the rest of the suite: ops dispatch
# is backend-keyed, not device-count-keyed, and jit on one device of
# eight compiles exactly as on one of one.
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import pytest

try:
    import hypothesis  # noqa: F401
except ImportError:
    _spec = importlib.util.spec_from_file_location(
        "_minihypothesis",
        os.path.join(os.path.dirname(__file__), "_minihypothesis.py"),
    )
    _mh = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_mh)
    _mh.install(sys.modules)


@pytest.fixture(autouse=True, scope="session")
def _isolated_autotune_cache(tmp_path_factory):
    """Point the autotune cache at a per-run temp file for ALL tests.

    Without this, tests resolving `blocks=None` would read whatever a
    prior benchmark run persisted to the developer's global cache
    (~/.cache/repro-vp/autotune.json) — kernel tilings, and thus the
    exact configurations under test, would depend on machine state.
    (tests/test_autotune.py re-points it per-test via monkeypatch.)
    """
    old = os.environ.get("REPRO_AUTOTUNE_CACHE")
    path = str(tmp_path_factory.mktemp("autotune") / "autotune.json")
    os.environ["REPRO_AUTOTUNE_CACHE"] = path
    yield
    if old is None:
        os.environ.pop("REPRO_AUTOTUNE_CACHE", None)
    else:
        os.environ["REPRO_AUTOTUNE_CACHE"] = old
