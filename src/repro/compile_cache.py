"""JAX's persistent compilation cache, at a place chosen outside the code.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``,
the cluster shard worker) call :func:`enable_compile_cache` once at
start.  Importing the package never does: tests compile for described
TPU topologies in-process, and those entries cannot be read back.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# A fixed path under the checkout: the directory is part of the cache
# key, so it must not come from a temp dir, a pid or the time.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and the
    config is left as it is; otherwise the cache goes to
    ``<checkout>/.jax_cache``, whatever the working directory.
    """
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
