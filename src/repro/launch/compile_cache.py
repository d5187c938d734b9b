"""Where JAX keeps its persistent compilation cache.

`use_compile_cache` is called by the entry points (`launch/train.py`,
`chip_smoke.py`) before their first compile, never at import. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
changed. Otherwise, on an accelerator, the cache goes to a fixed directory
inside the checkout, ``<repo>/.jax_cache`` (git-ignored). The directory is
part of what lets a later run find an entry, so it is never built from a
temp name, a pid or the time. XLA:CPU programs are left uncached: they
compile in seconds, and a cached one is tied to the host's CPU features
(loading it logs a feature-mismatch error).
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]
REPO_CACHE_DIR = REPO_ROOT / ".jax_cache"


def use_compile_cache() -> Optional[str]:
    """Turn the persistent cache on; returns its directory, or None when it
    stays off (CPU backend, or a package outside a checkout)."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax

    in_checkout = (REPO_ROOT / "pyproject.toml").is_file()
    if jax.default_backend() == "cpu" or not in_checkout:
        return None
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
