"""Where the serving entry points keep JAX's persistent compile cache.

A full-width decode program and one prefill program per shape take tens
of seconds each to compile; the cache lets a later process on the same
machine load them instead.  ``$JAX_COMPILATION_CACHE_DIR``, when set, is
used as it is (JAX reads it itself).  Otherwise the cache goes to one
fixed directory inside the checkout, which outlives the process and is
found again by the next one; a temporary or per-process directory would
be new to every process, so it would never hit.
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
