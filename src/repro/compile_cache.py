"""Where JAX keeps its persistent compilation cache for this checkout.

    from repro.compile_cache import enable_compile_cache
    path = enable_compile_cache()  # before the first compile

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins:
the cache goes there and this module names no directory.  Otherwise the
cache goes to ``<checkout>/.jax_cache`` — a fixed path (never made from a
temporary name, a PID or a time), because the path is part of what a later
run looks the cache up by.  ``.gitignore`` lists it.

JAX skips caching any compile shorter than
``jax_persistent_cache_min_compile_time_secs`` (1 s by default); most
kernels here compile faster than that, so the threshold is lowered to 0 —
here and nowhere else.

Nothing happens at import: entry points (``chip_smoke.py``,
``benchmarks/run.py``) call ``enable_compile_cache`` themselves.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = CHECKOUT / ".jax_cache"
ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
