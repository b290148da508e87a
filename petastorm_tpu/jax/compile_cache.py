"""Persistent XLA compile cache for the repo's chip scripts.

A cold ResNet-50 train step compiles for tens of seconds on a TPU. The chip
scripts (``chip_smoke.py``, ``bench_duty.py``) call
:func:`use_persistent_compile_cache` before their first compile so that the
next run of the same program loads the executable instead. The library never
calls it on import: where a cache lives is the caller's decision.
"""

from __future__ import annotations

import os

ENV_VAR = 'JAX_COMPILATION_CACHE_DIR'


def use_persistent_compile_cache(repo_root):
    """Turn on JAX's persistent compile cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets nothing. Otherwise the cache goes to ``<repo_root>/.jax_cache``: a
    fixed path, because the path is part of the cache key and a directory
    named after a pid, a temp name or the time would never be hit again.
    """
    import jax

    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    path = os.path.join(os.path.abspath(repo_root), '.jax_cache')
    jax.config.update('jax_compilation_cache_dir', path)
    return path
