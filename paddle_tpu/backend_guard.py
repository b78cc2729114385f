"""Backend bootstrap helpers.

``force_cpu_mesh(n_devices)``
    Re-point jax at the host-CPU platform with ``n_devices`` virtual
    devices (the mesh-emulation trick the reference's tests use for
    multi-device runs without a cluster, cf. SURVEY.md §4 note on
    ``xla_force_host_platform_device_count``).  Safe to call whether or
    not backends were already initialized: initialized backends are
    cleared so the forced platform takes effect.

``enable_compile_cache(default_dir)``
    The ONE place that turns on jax's persistent compilation cache
    (``chip_smoke.py``, ``bench.py``, ``tests/conftest.py``).
"""
from __future__ import annotations

import os
import re


def enable_compile_cache(default_dir: str,
                         min_compile_secs: float = 1.0) -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    no directory is set here; otherwise the cache lives at the FIXED
    ``default_dir`` (the path is part of the cache key, so a directory
    that moves never hits).  The autotune winners sit in the same
    directory (``ops/pallas/autotune.py``)."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", default_dir)
    return default_dir


def force_cpu_mesh(n_devices: int = 8):
    """Force the host-CPU platform with ``n_devices`` virtual devices.

    Returns the ``jax`` module, guaranteed to expose at least
    ``n_devices`` CPU devices on the next ``jax.devices()`` call.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    flag = f"--xla_force_host_platform_device_count={n_devices}"
    if "xla_force_host_platform_device_count" in flags:
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+", flag, flags)
    else:
        flags = (flags + " " + flag).strip()
    os.environ["XLA_FLAGS"] = flags

    import jax
    from jax._src import xla_bridge as _xb

    # If a backend was already initialized (a previous force_cpu_mesh
    # with a different count, or any earlier jax call), clear it FIRST:
    # `jax_num_cpu_devices` refuses updates while backends are live.
    if _xb.backends_are_initialized():
        jax.clear_caches()
        _xb._clear_backends()
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)
    return jax
