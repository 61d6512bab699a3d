"""JAX's persistent compilation cache for the command-line entry points.

A run on a fresh machine compiles every program it uses; with the cache
on, a later run that finds the directory again loads them instead.
:func:`enable` is called by ``chip_smoke.py`` and the launch and
benchmark ``main``s, never at import, so importing the library changes
no global JAX setting.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache: a fixed path, so every run of this checkout meets
# the entries of the runs before it
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and it stands; otherwise the cache goes to :data:`DEFAULT_DIR`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
