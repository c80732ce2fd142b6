"""JAX's persistent compilation cache for the entry points.

``use_compile_cache()`` is called from the ``main()`` of each entry point
(never at import): a compiled step program is then read back by the next
process, or the next in-process ``main()``, instead of compiled again.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and nothing else is set. Otherwise the cache lives in the fixed
``<repo>/.jax_cache``: the path is part of what makes a later run find an
entry, so it is never built from a temp name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent cache at its one directory; returns it."""
    import jax
    path = os.environ.get(ENV) or str(REPO_CACHE)
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
