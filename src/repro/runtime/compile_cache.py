"""Persistent XLA compilation cache: one fixed place per checkout.

Entry points (``chip_smoke.py``, ``examples/``, ``benchmarks/``) call
:func:`enable_compile_cache` before their first compile, so a second run on
the same machine loads compiled programs instead of recompiling them.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and this
module sets no other directory. Otherwise the cache lives at
``<repo>/.jax_cache`` (git-ignored). The path is part of each entry's
identity, so it is fixed: never a temporary directory, process id or time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the default cache directory: ``.jax_cache`` at the root of the checkout
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
