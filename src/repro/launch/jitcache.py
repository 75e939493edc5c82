"""Persistent-compilation-cache policy shared by every entry point.

A cold compile of the engine's training scan costs seconds on the CPU and
minutes at published widths on the chip, and every supervisor restart or
repeat invocation used to pay it again. JAX's persistent compilation cache
turns a re-trace of an identical program into a disk load. The cache's
path is part of its key, so it lives at one fixed place: where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that itself and nothing is
configured here; otherwise it is ``<checkout>/.jax_cache`` (git-ignored).

Call `enable_persistent_cache` before the first compile. It only updates
``jax.config``, so it never initialises a backend.
"""
from __future__ import annotations

import os

#: the fixed cache location when JAX_COMPILATION_CACHE_DIR is unset
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_persistent_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
