"""JAX's persistent compilation cache, at one fixed place per checkout.

A cold run on the chip compiles every prefill, decode and train-step program
of the model; the persistent cache lets the next process on the same machine
load them instead. Its directory is part of what a later run must find
again, so it never moves: ``$JAX_COMPILATION_CACHE_DIR`` when that is set
(JAX reads the variable itself), else ``<checkout>/.jax_cache`` (listed in
``.gitignore``).
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
