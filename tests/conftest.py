"""Test configuration.

JAX tests run on a virtual 8-device CPU mesh (the driver separately
dry-run-compiles the multi-chip path); set the flags before any jax import.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Pin jax to the CPU backend BEFORE any backend is initialized (conftest
# import time guarantees that): the tests never touch an accelerator.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(params=["memory", "sqlite", "log", "native"])
def db(request, tmp_path):
    """Multi-engine DB fixture: every db test runs against all engines —
    three durable (sqlite, log-structured, native C++) + memory
    (reference src/db/test.rs:127-144 pattern)."""
    from garage_tpu.db import open_db

    if request.param == "native":
        from garage_tpu import _native

        if not _native.available():
            pytest.skip("native library unavailable")
    d = open_db(str(tmp_path / "db"), engine=request.param)
    yield d
    d.close()
