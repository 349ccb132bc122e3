"""Persistent XLA compilation cache — restarts start hot from disk.

JAX writes every backend compile into a directory keyed on the HLO hash,
and an identical compile in a LATER process — a serving restart, a version
rollback re-warming the same architecture, the second benchmark run —
loads the executable instead of recompiling. This module is the one place
that decides WHERE that directory is:

- ``JAX_COMPILATION_CACHE_DIR`` set: whoever launched the process placed
  the cache. JAX reads the variable itself; nothing here touches
  ``jax_compilation_cache_dir``. An explicit ``cache_dir`` that names a
  different place raises — two answers to "where is the cache" split warm
  state across two directories.
- not set: the explicit ``cache_dir``, else ``<checkout>/.jax_cache`` next
  to the package. The path is part of what makes a later run hit, so it is
  derived from the package location and never from a temp dir, a pid or
  the time.
- not set, no ``cache_dir``, and the backend is the CPU: no cache. XLA:CPU
  logs a machine-feature mismatch error for every executable it loads back
  (seen on jaxlib 0.9.0), and CPU compiles are cheap; the default directory
  is for the accelerator, where a cold start costs minutes.

Whenever a cache is on, both persistence floors are lowered: by default JAX
only keeps compiles that took >= 1 s and are >= 64 KiB, and a serving
warmup full of small per-bucket forwards would persist NOTHING.
"""

from __future__ import annotations

import os
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_enabled_dir: Optional[str] = None


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache``: beside the package, the same on every call."""
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package), ".jax_cache")


def enable_persistent_compile_cache(
        cache_dir: Optional[str] = None) -> Optional[str]:
    """Turn the process's persistent compilation cache on and return its
    directory (resolved as the module docstring says; created if missing),
    or None where the docstring says there is none. Idempotent. Raises
    ``ValueError`` when ``cache_dir`` disagrees with
    ``JAX_COMPILATION_CACHE_DIR`` or with the directory already active in
    this process."""
    global _enabled_dir
    import jax

    from_env = os.environ.get(ENV_VAR)
    if cache_dir is not None:
        cache_dir = os.path.abspath(str(cache_dir))
    if from_env:
        from_env = os.path.abspath(from_env)
        if cache_dir is not None and cache_dir != from_env:
            raise ValueError(
                f"{ENV_VAR}={from_env} places the compile cache; cannot "
                f"retarget to {cache_dir}")
        cache_dir = from_env
    elif cache_dir is None:
        if jax.default_backend() == "cpu":
            return None
        cache_dir = default_cache_dir()
    if _enabled_dir is not None:
        if _enabled_dir != cache_dir:
            raise ValueError(
                f"persistent compile cache already active at {_enabled_dir}"
                f"; cannot retarget to {cache_dir}")
        return cache_dir
    os.makedirs(cache_dir, exist_ok=True)
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _enabled_dir = cache_dir
    return cache_dir


def persistent_compile_cache_dir() -> Optional[str]:
    """The active cache directory, or None when not enabled."""
    return _enabled_dir
