"""Test env: force a virtual 8-device CPU mesh.

Multi-chip hardware is not available in CI; sharding tests run on
`--xla_force_host_platform_device_count=8` CPU devices (the same trick the
reference uses for mesh emulation, cf. SURVEY.md §4 note).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# something may have imported jax (and captured JAX_PLATFORMS) before this
# conftest ran — override it at the config level too
jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache: compile-heavy 8-device-mesh tests
# dominate suite time; a warm cache turns repeat runs into disk reads.
# Safe under pytest-xdist (per-entry atomic file writes).  Follows
# JAX_COMPILATION_CACHE_DIR when set, else the fixed tests/.jax_cache.
from paddle_tpu.backend_guard import enable_compile_cache  # noqa: E402

enable_compile_cache(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), ".jax_cache"),
    min_compile_secs=0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.RandomState(0)
