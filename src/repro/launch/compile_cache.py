"""Where JAX's persistent compilation cache lives.

A cold chip run compiles a 48-block scan per (bucket, launch size, scheme);
the persistent cache lets the next process on the same machine load those
executables instead.  The cache key includes its directory, so the path is
fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
the variable itself and this module sets nothing), otherwise ``.jax_cache``
at the root of the checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def place_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
