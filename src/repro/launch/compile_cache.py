"""Where the entry points keep JAX's persistent compilation cache.

Called once by each entry point (``chip_smoke.py``, ``launch/train.py``,
``launch/serve.py``) before it compiles anything; the library and the
tests never call it. The cache key includes the directory, so the
directory must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache — fixed, and listed in .gitignore
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing; otherwise the cache goes to ``DEFAULT_DIR``.
    """
    import jax

    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
