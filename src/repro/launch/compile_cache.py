"""Where JAX keeps its persistent compilation cache.

One rule for every entry point (the ``repro.launch.*`` CLIs and
``chip_smoke.py``; tests never call it):

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and nothing
  here overrides it.
* otherwise: the cache goes to ``<checkout>/.jax_cache`` (git-ignored). A
  cache directory that moves is never hit again, so the path is fixed —
  never derived from a temp name, a PID or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout this package was imported from (src/repro/launch/ → root)
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module doc) and return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
