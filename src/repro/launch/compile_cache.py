"""Where compiled XLA programs persist between runs."""
from __future__ import annotations

import os
import pathlib

import jax
from jax.experimental.compilation_cache import compilation_cache

# <checkout>/.jax_cache: a fixed path, so a later run in the same checkout
# finds what an earlier one compiled.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
    there and nothing is changed; otherwise the cache goes to
    ``.jax_cache/`` at the root of the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_CACHE_DIR)
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        # JAX settles whether the cache is used at its first compile: a
        # program compiled before this call would leave it off.
        compilation_cache.reset_cache()
    return path
