"""Where JAX's persistent compile cache lives.

A served process compiles its search programs and the model's generate
step once; the persistent cache lets the next process on the same machine
load them instead. ``JAX_COMPILATION_CACHE_DIR`` is JAX's own setting: when
it is set, JAX uses it and nothing here overrides it. Otherwise the cache
goes to ``.jax_cache`` at the root of the checkout (git-ignored) — one
fixed path, never a temporary, per-process or per-run directory, so every
run from this checkout finds what earlier runs compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
