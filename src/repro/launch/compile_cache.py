"""Where JAX's persistent compilation cache lives.

Entry points call ``enable_compile_cache()`` once, before their first
compile: ``chip_smoke.py``, ``launch.train.main``, ``launch.serve.main``
and the ``benchmarks/`` command lines.  Importing a module never turns
the cache on.

* ``JAX_COMPILATION_CACHE_DIR`` set: that directory, and no other.
* unset: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).  The path
  is fixed: it is part of the cache key, so a directory named after a
  temporary, a pid or a time would never hit.

Every program is cached, however fast it compiled, so a second run of
the same phase compiles nothing new.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        DEFAULT_CACHE_DIR)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()      # re-read the directory at the next compile
    return path
