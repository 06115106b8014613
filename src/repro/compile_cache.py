"""JAX's persistent compilation cache, placed from outside or at one fixed
directory of the checkout.

Every entry point (``chip_smoke.py``, ``repro.launch.serve_mmo``,
``benchmarks/run.py``) calls ``enable_compile_cache()`` before it compiles
anything, so a second run of the same programs loads them from disk instead
of compiling again.  The cache directory is part of each entry's key, so it
must not move between runs: it is never built from a temp name, a pid or the
time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache — git-ignored; src/repro/compile_cache.py is two
# levels below the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
  """Turn the persistent compilation cache on; returns its directory.

  ``$JAX_COMPILATION_CACHE_DIR`` wins when set: JAX reads it itself and no
  other directory is set here.  Otherwise the cache lives in the checkout's
  ``.jax_cache/``.
  """
  path = os.environ.get(ENV_VAR)
  if not path:
    path = str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
  jax.config.update("jax_enable_compilation_cache", True)
  # the engine compiles many small programs (one per bucket and arena slot
  # program, most well under JAX's default 1 s threshold): keep them all
  jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
  return path
