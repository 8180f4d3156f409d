"""JAX's persistent compilation cache, kept at one fixed place.

``python -m repro`` and ``chip_smoke.py`` call :func:`enable` before
anything compiles, so a second run on the same machine reuses the compiled
kernels and steps instead of compiling them again.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing here
  touches the setting or names another directory.
* Otherwise the cache lives at ``<checkout>/.jax_cache`` — a fixed path
  (listed in ``.gitignore``), never one built from a temp name, a pid or a
  time, since a directory that moves between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
