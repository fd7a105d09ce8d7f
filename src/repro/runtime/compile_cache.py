"""Persistent XLA compilation cache for the entry points.

Called by the programs that compile (``chip_smoke.py``, the serving
launcher, the replica worker, the serving benchmarks) — never at import,
so tests keep jax's defaults.  When ``JAX_COMPILATION_CACHE_DIR`` is set,
jax already reads it and nothing is changed here.  Otherwise the cache
lives at a fixed path inside the checkout: the path is part of what makes
a later process find the entries again, so it never depends on a pid, a
time or a temporary directory.
"""

from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def use_compile_cache() -> str:
    """Point jax's persistent compilation cache at its directory; returns
    the directory in use."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
