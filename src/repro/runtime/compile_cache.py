"""JAX's persistent compilation cache, kept at one fixed path.

Entry points call `use_compile_cache` once, before their first compile;
importing `repro` never touches the cache.  The cache key includes the
directory, so the path must not move between runs: no temporary names,
process ids or times in it.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache(root: str) -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
    nothing is changed.  Otherwise the cache lives at `<root>/.jax_cache`
    (list it in `.gitignore`), so a second run from the same checkout reads
    what the first one wrote.
    """
    path = os.environ.get(ENV)
    if path:
        return path
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_entries(path: str) -> int:
    """Number of entries in the cache directory (0 before the first run)."""
    try:
        return sum(1 for e in os.scandir(path) if e.is_file())
    except FileNotFoundError:
        return 0
