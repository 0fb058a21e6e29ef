"""The one owner of jax's persistent compilation cache location.

Every process entry point that compiles (the trainers' CLIs, the serving
replica, the loadgen's in-process server, the root benches, ``chip_smoke.py``)
calls :func:`enable_compile_cache` once at start-up, before its first compile.

Where the cache lives:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax reads that variable itself at import,
  so this module touches no ``jax_compilation_cache_dir`` config at all — the
  directory is placed from outside (a benchmark driver, a CI cache mount) and
  child processes inherit it through the environment.
- unset: one fixed directory inside the checkout, derived from this package's
  location (never the CWD — trainers are run from scratch directories — and
  never a temp name, pid or time: the path is part of what makes a second run
  hit).

Import cost: ``os`` only; jax is imported inside the call, so backend-free
modules can reference this one lazily.
"""

from __future__ import annotations

import os

_PACKAGE_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# <checkout>/.jax_cache — the package's parent directory is the checkout root
# (listed in .gitignore).
DEFAULT_CACHE_DIR = os.path.join(os.path.dirname(_PACKAGE_DIR), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns the directory in use.

    Raises whatever ``os.makedirs`` / ``jax.config.update`` raise: a cache that
    silently failed to enable is a cold compile in every later run.
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Cache every program, not only those over jax's 1 s default: an engine's
    # many sub-second programs are most of a warm start.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
