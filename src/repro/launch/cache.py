"""Persistent XLA compilation cache at one fixed place.

`enable_compile_cache` is called by every top-level program that compiles
for the chip (``chip_smoke.py``, ``benchmarks/run.py``) before its first
compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its
own and nothing is set here.  Otherwise the cache lives in ``.jax_cache/``
at the root of the checkout (listed in ``.gitignore``): a fixed path, since
the path is part of the cache key and a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the in-checkout cache directory used when ENV_VAR is unset
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
