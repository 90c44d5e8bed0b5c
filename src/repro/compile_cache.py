"""Where JAX keeps its persistent compilation cache for this repo's entry
points.

``JAX_COMPILATION_CACHE_DIR``, when set, names the cache, and JAX reads it
itself; nothing else is set then.  Otherwise the cache lives at the fixed
path ``<checkout>/.jax_cache`` (gitignored).  The path is part of the
cache key, so it never depends on a temporary name or a process id.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
``enable_compile_cache()`` before their first compilation; importing the
package never touches the cache.
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir() -> str:
    """The cache directory: the environment variable if set, else the
    fixed in-checkout default."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    and return that directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
