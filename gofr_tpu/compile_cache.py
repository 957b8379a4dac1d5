"""Where JAX's persistent compilation cache lives.

One rule for every process of this repo (server, bench, chip smoke,
scripts):

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads the variable itself at
  import; nothing here (or anywhere else in the tree) writes
  ``jax_compilation_cache_dir``.
* unset — one fixed directory beside the package, ``<checkout>/.jax_cache``
  (git-ignored). Never a tempdir, a pid or a timestamp: a directory that
  moves between runs never hits.

JAX initializes the cache lazily at the first compile and keeps that
directory for the life of the process, so :func:`enable_compile_cache`
belongs at the process entry point, before the first jit. JAX's own
thresholds (``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS``, default 1 s)
decide which programs persist.

Importing this module does not import jax.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def resolve_compile_cache_dir() -> str:
    """The cache directory this process uses: the JAX variable when set,
    else the fixed checkout-relative default."""
    return os.environ.get(ENV_VAR) or os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX at :func:`resolve_compile_cache_dir` — a no-op beyond the
    lookup when the JAX variable is set. Returns the directory."""
    path = resolve_compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
