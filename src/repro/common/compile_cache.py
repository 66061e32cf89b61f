"""JAX's persistent compilation cache, in one fixed place per checkout.

Entry points call `enable()` once at start-up, never at import: a full-width
model compiles for about a minute, and a process that finds the program in
the cache skips that.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: Cache directory when the environment names none: a fixed path in the
#: checkout (git-ignored), so every run of this checkout finds the last one's
#: entries.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable() -> str:
  """Turn the persistent compilation cache on; returns its directory.

  Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
  nothing is set here; otherwise the cache goes to `DEFAULT_DIR`.
  """
  where = os.environ.get(ENV_VAR)
  if where:
    return where
  jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
  return str(DEFAULT_DIR)
