"""JAX persistent compilation cache for the command-line entry points.

Called by ``main()`` of the launchers and by ``chip_smoke.py`` before their
first compile; never at import time and never from tests. A directory named
by ``JAX_COMPILATION_CACHE_DIR`` wins (JAX reads it itself); otherwise the
cache lives at the fixed path ``<checkout>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns that path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
