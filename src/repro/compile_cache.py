"""Where the persistent XLA compilation cache lives.

A cold chip run compiles every kernel and jitted keyswitch plan, so the
scripts that drive the chip (``chip_smoke.py``, ``benchmarks/run.py``)
turn JAX's persistent compilation cache on.  The cache path is part of
each entry's key, so it is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that variable itself, and nothing here
overrides it), otherwise ``<checkout>/.jax_cache`` (git-ignored).

Library imports never touch the cache; scripts call :func:`enable`.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"


def enable(checkout: str | os.PathLike) -> str:
    """Turn the persistent compilation cache on for this process and
    return its directory (see the module docstring for the choice)."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax

    path = str(pathlib.Path(checkout).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
