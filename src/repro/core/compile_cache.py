"""Where this repository's scripts keep JAX's persistent compilation
cache.

Scripts (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``) call
:func:`enable_compile_cache` once at start-up; importing the library
never touches the cache.  A second run in the same checkout then loads
its compiled programs instead of compiling them again.
"""
from __future__ import annotations

import os

import jax

#: ``<checkout>/.jax_cache``: a fixed path, so every run in one
#: checkout finds what the last one stored.
DEFAULT_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing is changed; otherwise the cache goes to ``DEFAULT_DIR``.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
