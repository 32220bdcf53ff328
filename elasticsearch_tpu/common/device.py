"""Process start-up on the device: where compiled programs are cached,
and which device the process found.

One process owns one chip; every entry point that compiles (``estpu``,
``chip_smoke.py``, ``benchmarks/run.py``, ``__graft_entry__.py``) goes through
:func:`ensure_compile_cache` before its first compile, and
``Node.start()`` calls it for them.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the checkout's own cache directory (git-ignored). The path is part of
#: the cache key's surroundings: a directory that moves never hits, so it
#: is fixed here.
REPO_COMPILE_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def ensure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a stable place → the
    directory in use. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has
    already read it and nothing is configured here; otherwise the cache
    lives at :data:`REPO_COMPILE_CACHE`. JAX's own thresholds (which
    compiles are worth writing) stay at their defaults."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    path = str(REPO_COMPILE_CACHE)
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def describe_devices() -> dict:
    """``{"platform", "kind", "count"}`` of the default backend, as JAX
    reports it. Initialises the backend: on a machine whose JAX is set
    to a TPU that is not there, this raises."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
