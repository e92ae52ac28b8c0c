"""JAX's persistent compilation cache for the command-line entry points.

A run on a fresh machine compiles every program it dispatches; the
persistent cache lets the next process on the same disk skip that.  A
later process finds the entries only if it looks in the same directory,
so the cache lives at one fixed path: ``JAX_COMPILATION_CACHE_DIR`` when
the environment sets it (JAX reads that itself, and this module leaves
it alone), else ``.jax_cache`` at the repository root.

Entry points call ``use_compile_cache()`` under their ``__main__``
guard — never at import time, and never from tests.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory;
    returns the directory in use."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
