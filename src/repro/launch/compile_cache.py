"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so the directory must not move
between runs: it is ``$JAX_COMPILATION_CACHE_DIR`` when that is set
(JAX reads the variable itself), and otherwise the fixed ``.jax_cache``
directory at the root of the checkout.  Entry points call
``enable_compile_cache()`` once at start-up; importing this module
changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache(root: Optional[Path] = None) -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.  ``root`` is the checkout (default: the one
    this package lives in)."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    path = str(Path(root or REPO_ROOT) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
