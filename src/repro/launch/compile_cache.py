"""Persistent XLA compilation cache for the command-line entry points.

Called from each ``main()`` (and ``chip_smoke.py``), never at import: a
library import must not change process-wide JAX configuration.

The cache's directory is part of its key, so it must not move between runs:
it is either what ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that
variable itself, and nothing is set here) or the fixed ``.jax_cache``
directory at the root of this checkout.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

#: ``<checkout>/.jax_cache`` — listed in ``.gitignore``.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at a fixed directory.

    Returns the directory set here, or ``None`` when the environment's
    ``JAX_COMPILATION_CACHE_DIR`` already places the cache.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
