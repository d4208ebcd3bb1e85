"""Where JAX's persistent compilation cache lives (one rule for every entry
point that compiles for the chip: ``chip_smoke.py`` and each ``bench.py``
child call :func:`enable_compile_cache` before their first compile).

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and that reading
  stands; no code here sets another directory.
* unset: the cache goes to the fixed ``<repo>/.jax_cache`` (gitignored).  The
  path is part of the cache key, so it is never built from a tmpdir, a pid or
  the time.
"""

from __future__ import annotations

import os

#: the fixed fallback location: ``<repo>/.jax_cache``
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
