"""Where JAX's persistent compilation cache lives.

One rule for every entry point (``chip_smoke.py``, the example trainers,
``benchmark/run.py``, ``tests/conftest.py``): if
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no code
sets another directory; otherwise the cache is ``<checkout>/.jax_cache``
(git-ignored). The path is part of the cache key, so it is fixed — never
built from a temp dir, a pid or a time. Call :func:`configure` before
the process's first compile: JAX latches the cache decision there.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = 'JAX_COMPILATION_CACHE_DIR'
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    '.jax_cache',
)


def configure() -> str:
    """Apply the rule above; returns the directory in effect."""
    if not os.environ.get(ENV_VAR):
        jax.config.update('jax_compilation_cache_dir', DEFAULT_DIR)
    return current_dir()


def current_dir() -> str | None:
    """The cache directory JAX is configured with (None: no cache)."""
    return jax.config.jax_compilation_cache_dir
