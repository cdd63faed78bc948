"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, the ``repro.launch`` CLIs,
``benchmarks/run.py``) call :func:`enable_compile_cache` before their
first compile; importing the library never touches the cache. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
else is configured here. Otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` — fixed because the directory is part of
what a later run must find again, so it is never a temp, pid or
time-based path.
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` (this file is ``src/repro/compat/cache.py``)
DEFAULT_DIR = str(pathlib.Path(__file__).resolve().parents[3]
                  / ".jax_cache")


def compile_cache_dir() -> str:
    """The directory the persistent compilation cache uses."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
