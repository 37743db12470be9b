"""Where JAX keeps its persistent compilation cache.

Entry-point scripts call ``enable(root)`` once, before their first
compile; the library never does so on import. A cache only hits when it
is found at the same path again, so the path is fixed: the directory
named by ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the
variable itself, and no other directory is set here), otherwise
``<root>/.jax_cache`` inside the checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"


def enable(root) -> str:
    """Turn the persistent compilation cache on and return its directory.

    Every compile is cached, not only those over JAX's default one-second
    floor: a chip call pays for each small kernel and jitted helper too.
    """
    path = os.environ.get(ENV)
    if not path:
        path = str(pathlib.Path(root).resolve() / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
