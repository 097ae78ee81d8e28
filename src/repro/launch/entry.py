"""Start-up helpers for the entry points (``launch/cluster.py``,
``chip_smoke.py``). Library code never calls them: importing a module of
this package must not change JAX's configuration."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# The path is part of the persistent cache's key, so it is fixed: a cache
# directory that moves with the working directory never hits.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    JAX itself reads ``JAX_COMPILATION_CACHE_DIR`` when it is set, and then
    nothing else is configured here; otherwise the cache is kept in
    ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def device_info() -> dict:
    """The device a run used, as JAX reports it."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}
