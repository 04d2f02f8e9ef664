"""Where the entry points keep JAX's persistent compilation cache.

Called by each entry point (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.train``) before its first compile, never at import: a
library that sets a process-wide cache path on import would move every
caller's cache.
"""

from __future__ import annotations

import os
from pathlib import Path

# the cache key includes the directory, so the default is one fixed path
# inside the checkout (git ignores it)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    given = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if given:
        return given
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
