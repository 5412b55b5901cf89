"""Where the persistent XLA compile cache lives: one rule, one place.

Cold start is weights-to-HBM plus XLA compilation, and a warm persistent
cache turns the second into disk reads. The cache directory is part of every
entry's key, so it must not move between runs:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it natively and nothing in
  this repo sets or overrides a directory.
- unset: the entry points (``tpurun``, ``bench.py``, ``chip_smoke.py``) call
  :func:`place_compile_cache` before anything imports JAX. It exports the
  one fixed, git-ignored directory inside the checkout through that same
  variable, so this process and every container it spawns agree on it.

Library code (``LLMEngine``, the executor, examples, tests) never enables or
places the cache.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the fixed fallback: no host fingerprint, pid, time or temporary name
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".xla_cache"


def place_compile_cache() -> str:
    """Return the compile-cache directory, exporting the in-checkout default
    when the environment names none. Jax-free; call before importing JAX
    (JAX reads the variables once, at import)."""
    path = os.environ.get(CACHE_DIR_ENV)
    if not path:
        path = os.environ[CACHE_DIR_ENV] = str(DEFAULT_CACHE_DIR)
    # keep every program, however fast it compiled: under JAX's default
    # (keep what took >= 1 s) a program near the threshold is written on
    # some runs and not others, and a warm run still reports misses
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    return path
