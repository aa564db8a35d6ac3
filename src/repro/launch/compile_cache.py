"""JAX's persistent compilation cache, placed from outside or at a fixed path.

A cold run compiles every step program, decode-block bucket and warm-up
shape; with the cache on, the next process on the same machine reads
them back.  The directory is part of what makes an entry findable, so
it is never derived from a temporary name, a pid or the time:

- ``$JAX_COMPILATION_CACHE_DIR``, when set, is the cache and no other
  directory is configured;
- otherwise the cache lives at ``<checkout>/.jax_cache`` (git-ignored).

Entry points call :func:`enable_compile_cache` first thing
(``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks/run.py``);
tests do not.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
