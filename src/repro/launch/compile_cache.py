"""Where JAX keeps compiled programs between runs.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this module
leaves it alone.  Otherwise the entry points (``chip_smoke.py``,
``bench_serving``, ``dryrun``) point the persistent compilation cache at
one fixed directory inside the checkout, ``<repo>/.jax_cache``: never a
temporary name, a pid or a time, so a later run finds what an earlier
one compiled.
"""

from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "configure_compile_cache"]
