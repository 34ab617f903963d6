"""JAX's persistent compilation cache for the entry points.

``enable()`` is called by ``launch.serve.main`` and ``chip_smoke.py`` — by
entry points only, never while a module is imported, so a test process keeps
whatever cache setting it started with.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and that
directory is the cache: nothing else is set in code.  Otherwise the cache
lives at a fixed path inside the checkout, ``<repo>/.jax_cache`` (listed in
``.gitignore``).  The path is part of the cache's key, so it never depends
on a temp directory, a pid or the time.
"""

from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


__all__ = ["enable", "ENV", "DEFAULT_DIR"]
