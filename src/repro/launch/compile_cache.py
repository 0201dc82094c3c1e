"""Where JAX keeps its persistent compilation cache for this repository.

A cold compile of the paper-scale step takes about a minute, and every run
on a fresh machine starts cold. :func:`enable_compile_cache` is called at
the top of each entry point (``chip_smoke.py``, the examples,
``benchmarks/run.py``):

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
  cache directory is set here;
* otherwise the cache goes to ``<checkout>/.jax_cache`` — a fixed path,
  because the directory is part of what a later run must find again
  (it is listed in ``.gitignore``).

Either way the checkout's own path is cut from the source locations JAX
records. A Pallas TPU kernel is embedded in the program as a serialized
Mosaic module that keeps its source locations, and the cache key hashes
it, so without the cut two checkouts of the same commit at different
paths never share an entry.

Tests never call it: they compile on the CPU, and compiles for a
described TPU cannot be read back from the cache without one.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"
SOURCE_PREFIX = "^" + re.escape(str(CHECKOUT) + os.sep)


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      SOURCE_PREFIX)
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
