"""Persistent XLA compilation cache setup, shared by every entry point.

One helper so the suite (tests/conftest.py), the driver entries
(__graft_entry__.py), and the bench harness (bench.py) cannot drift on the
cache location or the min-compile-time threshold (JAX's 1.0 s default
would silently skip the sub-second tiny-preset programs the suite and
dryrun compile most — and those recur by the hundred across the suite's
engine builds, so the threshold here is 0: cache every compile).

The cache is SAME-MACHINE only — serialized executables embed host CPU
features — so it lives in the (gitignored) repo-root ``.jax_cache/``;
override with ``JAX_COMPILATION_CACHE_DIR``.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_persistent_compile_cache(min_compile_secs: float = 0.0) -> str:
    """Point jax at the repo's persistent compile cache; returns the dir.

    Call any time before the programs of interest compile (the cache is
    consulted per-compile, not at backend init). The directory is
    ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``
    — never a temporary or per-process name: the path is part of the
    cache key, so a directory that moves never hits.
    """
    import jax

    cache = os.environ.get(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(_REPO_ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    return cache
