"""JAX's persistent compilation cache at a fixed path per checkout.

A program's entry point (never an import) calls ``enable_compile_cache``,
so a second run on the same machine loads its compiled programs instead
of compiling them again.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already reads it and no other directory is set in code.  Otherwise
the cache lives at ``<checkout>/.jax_cache`` (git-ignored): a fixed path,
because the path is part of what a cache entry is found by.
"""
from __future__ import annotations

import os
from typing import Mapping, Optional

#: the checkout root: src/repro/launch/compile_cache.py -> three levels up.
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def cache_dir(environ: Optional[Mapping[str, str]] = None
              ) -> "tuple[str, bool]":
    """(cache directory, whether code must set it): the environment's
    directory when ``JAX_COMPILATION_CACHE_DIR`` is set (JAX reads it
    itself), else the checkout's fixed ``.jax_cache``."""
    env = os.environ if environ is None else environ
    if env.get(ENV_VAR):
        return env[ENV_VAR], False
    return DEFAULT_DIR, True


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``cache_dir()`` and
    return the directory."""
    path, must_set = cache_dir()
    if must_set:
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
