"""JAX's persistent compilation cache: the one place its directory is set."""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed in-checkout default; the path is part of the cache key, so it must
#: not move between runs (never derived from a temp name, pid or time)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is that directory and no other
    is set; otherwise the cache lives at ``<checkout>/.jax_cache`` (listed
    in ``.gitignore``).  Returns the directory in use.
    """
    path = Path(os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path
