"""JAX's persistent compilation cache at one fixed place.

Called by the entry points (`repro.launch.serve`, `benchmarks/run.py`,
`chip_smoke.py`), never on import and never by tests.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout holding this source tree (``src/repro/common/`` is three down)
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Keep compiled programs across processes; returns the cache directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is changed here.  Otherwise the cache is ``<checkout>/.jax_cache``
    (ignored by git): a fixed path, so a later process finds what an
    earlier one compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
