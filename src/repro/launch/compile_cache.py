"""Where JAX's persistent compilation cache lives.

Called from the ``main()`` of each entry point that runs on the chip, never
at import.  ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
wins; otherwise the cache goes to ``.jax_cache/`` at the root of the
checkout — a fixed path, so a later run of the same checkout finds it again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return jax.config.jax_compilation_cache_dir
