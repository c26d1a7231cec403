"""Where JAX's persistent compilation cache lives — one rule for every
entry point (``launch/serve.py``, ``chip_smoke.py``, ``benchmarks/run.py``).

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it at start-up and the
cache goes there; nothing else is set here.  Otherwise the cache goes to a
fixed directory inside the checkout, ``<repo>/.jax_cache/`` (git-ignored):
the path is part of the cache key, so a fixed one is what lets a later
process in the same checkout find the programs an earlier one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Place the compilation cache by the rule above; returns its path."""
    env = os.environ.get(ENV, "").strip()
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
