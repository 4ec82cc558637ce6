"""Persistent XLA compilation cache for the repo's entry points.

Compiling the TM programs for a TPU takes longer than running them a few
times, so entry points (``chip_smoke.py``, ``launch/tm_serve.py``)
turn on JAX's persistent compilation cache before their first compile. Library code never calls this: importing
``repro`` changes no JAX configuration.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, inside the checkout: the cache key includes the path, so a
# directory that moved between runs would never hit
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX (which reads it
    itself) and no other cache is set; otherwise the cache lives at the
    fixed in-checkout path ``DEFAULT_DIR`` (gitignored).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
