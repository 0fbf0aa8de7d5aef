"""The one place the persistent XLA compilation cache is configured.

First compiles on the chip cost seconds to minutes; a persistent cache makes
every later launch start hot. The cache directory is part of the cache key's
lookup, so it must not move between runs:

- where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this
  module sets no directory in code — whoever runs the program (a harness, a
  deployment) places the cache;
- otherwise the cache is ``<checkout>/.jax_cache``, derived from the
  package's own path (never from ``~``, a temporary name, a pid or the
  time), and listed in ``.gitignore``.
"""

from __future__ import annotations

import os
from typing import Optional

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache: omldm_tpu/utils/compile_cache.py -> three levels up
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache(mode: str = "on") -> Optional[str]:
    """Turn the persistent compile cache on (or, ``mode="off"``, off) and
    return the directory in use (``None`` when off). Call before the first
    compilation: jax binds the cache on first use."""
    import jax

    if mode not in ("on", "off"):
        raise ValueError(
            f"compile cache mode must be on|off, got {mode!r}; place the "
            f"directory with {CACHE_DIR_ENV}"
        )
    if mode == "off":
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    placed = os.environ.get(CACHE_DIR_ENV)
    if placed:
        return placed
    os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
