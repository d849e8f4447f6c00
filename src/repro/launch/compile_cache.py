"""Where compiled programs persist between runs.

JAX's persistent compilation cache keys entries by, among other things,
the cache directory, so a directory that moves between runs (a temporary
name, a process id, a time stamp) never hits.  Entry points call
:func:`use_compile_cache` once, before their first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

# fixed and inside the checkout (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_compile_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets nothing; otherwise the cache goes to :data:`DEFAULT_DIR`."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
