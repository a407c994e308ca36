"""Where the entry points keep JAX's persistent compilation cache."""

from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIRNAME = ".jax_cache"


def enable_compile_cache(root) -> str:
    """Turn on the persistent compilation cache for one entry point.

    Call it from ``main()``, never at import: tests and library users keep
    JAX's defaults.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
    it itself and nothing else is configured.  Otherwise the cache goes to
    the fixed directory ``<root>/.jax_cache`` (``root``: the checkout the
    entry point lives in) -- a fixed path, because the directory is part
    of what a later run must find again.  Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(pathlib.Path(root).resolve() / CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
