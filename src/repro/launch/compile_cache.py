"""JAX's persistent compilation cache for the entry points.

Called from the ``main()`` of ``serve``, ``train`` and ``kernel_tune`` and from
``chip_smoke.py`` — never at import, never in tests (a test that compiles for
a described, unattached chip would write entries no later process can read).
"""
from __future__ import annotations

import os
from pathlib import Path

# <repo>/.jax_cache (gitignored). A fixed path, so a second run finds what the
# first one compiled.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and this
    sets no directory of its own; otherwise the cache goes to
    :data:`REPO_CACHE_DIR`. Every program is cached, not only those that took
    over a second to compile: kernels and decode steps compile fast but many
    times over."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
