"""JAX's persistent compilation cache for the repository's entry scripts.

Compiling the production-width programs takes minutes; the persistent cache
lets a second run of the same script skip that. The cache directory is part
of the cache key, so it must not move between runs: where the environment
sets ``JAX_COMPILATION_CACHE_DIR``, JAX uses that directory and this module
sets no other; otherwise the cache lives at one fixed path inside the
checkout, ``<repo>/.jax_cache`` (git-ignored).

Entry scripts (``chip_smoke.py``, ``examples/``, ``benchmarks/run.py``) call
:func:`setup_compile_cache` once at start-up. Library code and tests do not.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def cache_dir() -> str:
    """The directory the persistent compilation cache uses: the
    ``JAX_COMPILATION_CACHE_DIR`` environment variable when set, else
    :data:`DEFAULT_DIR`."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`cache_dir` and
    return it. With the environment variable set, JAX already reads it and
    the configuration is left alone."""
    import jax

    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
