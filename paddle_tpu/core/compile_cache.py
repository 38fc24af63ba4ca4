"""Persistent XLA compile cache, placed from outside.

Entry points call :func:`enable_compile_cache` once before their first
compile — ``chip_smoke.py``, ``benchmarks/run.py``, ``start_serving_server`` and the
``distributed.launch`` launcher (for its workers). It is never called at
``import paddle_tpu``: a library import must not decide where a process
writes.

Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing
is set in code. Otherwise the cache sits at the fixed ``<checkout>/.jax_cache``
(git-ignored): the directory is part of the cache key, so a temp name, a pid
or a timestamp in it would never hit.
"""

from __future__ import annotations

import os

__all__ = ["compile_cache_dir", "enable_compile_cache"]

_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """Where this process's compile cache lives: the environment's choice,
    else ``<checkout>/.jax_cache`` (the directory holding ``paddle_tpu/``)."""
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return os.environ.get(_ENV) or os.path.join(checkout, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory."""
    path = compile_cache_dir()
    if not os.environ.get(_ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
