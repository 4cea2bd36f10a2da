"""JAX's persistent compilation cache, placed from outside or at a fixed
path inside the repository.

Call `enable_compile_cache` at the top of a program's ``main()``, never at
import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing else is set here; otherwise the cache lives at ``<repo>/.jax_cache``
(listed in ``.gitignore``).  The path is part of the cache key, so it is
fixed: never derived from a temporary directory, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
