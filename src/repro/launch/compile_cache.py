"""Where the entry points keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, names the cache and is left to
JAX, which reads it itself.  Otherwise the cache goes to one fixed
directory inside the checkout, :data:`DEFAULT_DIR` (listed in
``.gitignore``): the path is part of the cache's key, so it is never built
from a temporary name, a PID or the time.  Library modules never call
this; each entry point's ``main`` calls it first.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout's own cache directory (<repo>/.jax_cache)
DEFAULT_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    or, when that is unset, at :data:`DEFAULT_DIR`; return the directory.

    The unset case also exports the variable, so processes this one spawns
    (cluster ranks) use the same directory."""
    import jax

    path = os.environ.get(ENV) or DEFAULT_DIR
    os.environ[ENV] = path
    jax.config.update("jax_compilation_cache_dir", path)
    return path
