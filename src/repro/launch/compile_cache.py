"""Persistent XLA compile cache for the entry points.

A full-width model compiles for tens of seconds; every fresh process on
a chip would pay that again.  ``enable()`` is called first thing by the
entry points (``chip_smoke.py``, ``launch/serve.py``, the benchmarks),
never on import, so library users and the tests keep JAX's own default.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
  here.
* Otherwise the cache lives at ``<checkout>/.jax_cache`` (gitignored).
  The path is fixed on purpose: it is part of what the cache is keyed
  by, so a per-run temp dir would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
ENV = "JAX_COMPILATION_CACHE_DIR"


def enable() -> str:
    """Turn the persistent compile cache on; return its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
