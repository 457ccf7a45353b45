"""JAX's persistent compilation cache, as the entry points turn it on."""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compilation_cache() -> str:
    """Keep compiled programs across processes; returns the directory.

    ``$JAX_COMPILATION_CACHE_DIR`` where it is set, else the fixed
    ``<checkout>/.jax_cache`` (git-ignored): the directory is part of
    each entry's key, so it must not move between runs.  Entry points
    call this before their first compile; importing does nothing.
    """
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(CHECKOUT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
