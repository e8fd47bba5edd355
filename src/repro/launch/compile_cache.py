"""JAX's persistent compilation cache for the entry points.

`enable()` is called by the launchers (`chip_smoke.py`,
`repro.launch.serve`) before their first compile — never on import, so
tests and library users keep JAX's own default. Where the environment
sets `JAX_COMPILATION_CACHE_DIR`, JAX reads it itself and nothing is set
here. Otherwise the cache lives at one fixed directory inside the
checkout (git-ignored): the directory is part of every entry's key, so a
path made from a temp name, a pid or the time would never hit again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
