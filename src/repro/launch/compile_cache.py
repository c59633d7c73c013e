"""Where JAX keeps its persistent compilation cache for this checkout.

    from repro.launch.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()

Called by the entry points (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``), never by tests or at import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this sets
no other directory.  Otherwise the cache goes to ``<checkout>/.jax_cache``:
a fixed path, because the path is part of what a later process must find
again (a temporary, pid- or time-derived directory never hits).
"""

from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    cache_dir = os.environ.get(ENV) or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
