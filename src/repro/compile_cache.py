"""Where the repo's entry points keep JAX's persistent compilation cache.

A cache hit needs the same directory every run (the path is part of the
cache key), so the cache goes where ``JAX_COMPILATION_CACHE_DIR`` says when
it is set — JAX reads that variable itself — and otherwise at one fixed
directory inside the checkout.  Called by `chip_smoke.py` and
`benchmarks.run`; importing `repro.core` never touches the cache.
"""

from __future__ import annotations

import os

import jax

CACHE_DIRNAME = ".jax_cache"


def use_compile_cache(checkout: str) -> str:
    """Enable the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(checkout), CACHE_DIRNAME)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
