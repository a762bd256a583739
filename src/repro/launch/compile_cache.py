"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable` before their first compile.  A caller's
``JAX_COMPILATION_CACHE_DIR`` wins (JAX reads the variable itself, and
nothing else is set); without it the cache sits at a fixed path inside
the checkout, ``.cache/jax``, so a second run from the same checkout
finds what the first one compiled.
"""

from __future__ import annotations

import os

import jax

#: <checkout>/.cache/jax (this file is <checkout>/src/repro/launch/...)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".cache", "jax")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
