"""Where JAX's persistent compilation cache lives.

Entry points call :func:`use_compile_cache` once, before their first
compile.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins (JAX reads that
variable itself).  Otherwise the cache goes to one fixed directory inside
the checkout, ``<repo>/.jax_cache`` (listed in ``.gitignore``): a fixed
path, because the path is part of what a cached entry is found by.

Either way an entry is also found by its instructions' metadata (the
``op_name`` scope paths and source lines).  JAX leaves metadata out of the
key by default, and then a step compiled by a tree with other named
scopes (``engine.PHASES``) but the same operations is loaded with that
tree's stale ``op_name``s, which is what a profile of the step is read by.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "use_compile_cache"]

#: the checkout's own cache directory (``src/repro/utils`` -> repo root)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory, with
    metadata in the key; returns the directory in use."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
