"""Where JAX's persistent compilation cache lives.

A cache directory is part of every entry's key, so it must not move between
runs: when `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
module sets nothing; otherwise the cache goes to one fixed directory inside
the checkout (`<repo>/.jax_cache`, ignored by git).
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir_to_set(environ: Mapping[str, str] = os.environ
                     ) -> Optional[str]:
    """The directory to configure in code: None when the environment
    already names one, else the fixed in-checkout path."""
    if environ.get(ENV):
        return None
    return str(REPO_CACHE)


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on before the first compile;
    returns the directory in use."""
    path = cache_dir_to_set()
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return path or os.environ[ENV]
