"""Where JAX keeps its persistent compilation cache.

Entry points call ``use_compile_cache()`` first thing in ``main()``;
importing this module changes nothing.  A directory set from outside in
``JAX_COMPILATION_CACHE_DIR`` wins and JAX reads it itself; otherwise the
cache lives at the fixed path ``<repo>/.jax_cache``, so that the next
run of any entry point finds what this one compiled — a temp name, a pid
or a time in the path would start every run cold.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (unless
    the environment already names one) and return that directory."""
    outside = os.environ.get(ENV_VAR)
    if outside:
        return outside
    path = str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
