"""JAX's persistent compilation cache for the entry points.

``chip_smoke.py`` and ``python -m
mpi_grid_redistribute_tpu.service`` call :func:`enable` before their
first compile; nothing calls it at import. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here. Otherwise the cache lives at the fixed ``<checkout>/.jax_cache``
(listed in ``.gitignore``): the path is part of what a later run looks
up, so it never depends on a temporary name, a pid or the time.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR

