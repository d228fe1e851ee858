"""JAX persistent compilation cache for the entry points.

``serve``, ``loadtest`` and ``chip_smoke.py`` call :func:`enable_compile_cache`
before their first compile.  JAX itself reads ``JAX_COMPILATION_CACHE_DIR``;
where that is set, nothing is changed here.  Otherwise the cache lives at
the fixed path ``<checkout>/.jax_cache`` (gitignored): the directory is part
of the cache key, so a temporary or per-process path would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Point JAX's compilation cache at ``<checkout>/.jax_cache`` unless
    ``JAX_COMPILATION_CACHE_DIR`` is set.  Returns the directory in use
    (``None`` only if JAX has none)."""
    import jax

    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return jax.config.jax_compilation_cache_dir
