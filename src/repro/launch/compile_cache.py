"""Persistent XLA compile cache for the entry points.

A cold start at published widths spends much of its time compiling: one
serve program per budget bucket, plus prefill, synopsis build and the
slot writes.  JAX's persistent cache keeps those programs across
processes.  Its directory is part of what makes an entry findable again,
so it never moves: ``JAX_COMPILATION_CACHE_DIR`` when the environment
sets it (JAX reads that variable itself, and nothing is set here), else
``.jax_cache/`` at the root of the checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
  """Turn the persistent compile cache on before the first compile;
  returns the directory it lives in."""
  env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
  if env:
    return env
  import jax  # noqa: PLC0415 — deferred so importing this is device-free
  jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
  return str(CHECKOUT_CACHE_DIR)
