"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (``chip_smoke.py``, the examples, the benchmark scripts)
call :func:`enable_compile_cache` once before they compile anything, so
a second run of the same bucket programs, stream cores and decode steps
loads them instead of compiling them again.  Library code and tests
never call it: importing the package changes no JAX setting.
"""

from __future__ import annotations

import os

# the checkout root: src/repro/compile_cache.py -> ../..
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is where JAX already keeps
    the cache; nothing else is set then.  Otherwise the cache goes to
    ``.jax_cache/`` at the repository root — a fixed path, because the
    directory is part of what makes a later run find an entry.

    Every program is kept, however fast it compiled: JAX's default keeps
    only those that took a second or more, which leaves out the many
    small stream-core and per-tick programs that together take most of
    a cold streaming pass."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
