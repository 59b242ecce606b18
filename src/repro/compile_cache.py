"""Where JAX keeps its persistent compilation cache for this repository.

The cache key includes the directory, so a directory that moves (a temp
dir, a pid or a timestamp in the path) never hits.  The rule:

* ``$JAX_COMPILATION_CACHE_DIR`` set -- JAX reads it itself; nothing here
  names a directory;
* unset -- one fixed path inside the checkout, ``<repo>/.jax_cache``
  (gitignored).

Serving compiles many small programs (one per padded chunk shape, per
tenant config), so the minimum compile time worth caching is lowered to 0.
The test suite never calls :func:`enable`.
"""

from __future__ import annotations

import os
from typing import Mapping, Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def directory_to_set(environ: Mapping[str, str] = os.environ
                     ) -> Optional[str]:
    """The directory :func:`enable` sets in code: None when the environment
    already names one (JAX applies it), else the fixed in-checkout path."""
    return None if environ.get(ENV_VAR) else REPO_CACHE_DIR


def enable() -> str:
    """Turn the persistent cache on for this process; returns its path."""
    path = directory_to_set()
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir
