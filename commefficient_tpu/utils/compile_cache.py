"""Where the persistent XLA compile cache lives.

A cold compile of one ResNet-9 sketched round takes minutes on the chip,
so every entry point keeps JAX's persistent compilation cache on. The
directory is placed from OUTSIDE the program when the environment sets
``JAX_COMPILATION_CACHE_DIR`` (JAX reads that variable itself; nothing is
set in code then); otherwise it is ``<checkout>/.jax_cache`` — a fixed
path derived from the package's location, never from a temp name, pid or
time, so a second process of the same checkout finds the first one's
programs.
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory (see module
    docstring) and return the directory in effect. Call first thing in an
    entry point, before anything compiles."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
