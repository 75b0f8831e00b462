"""Persistent XLA compilation cache: one placement rule for every process.

Recovery is compile-dominated once restore is overlapped: a restarted
(or re-meshed) worker re-traces and re-compiles the train step before
its first step runs. XLA's persistent compilation cache turns that into
a disk read — but only if every process of the job, and every later
run, points at the SAME directory: the directory is part of the cache
key, so a cache that moves never hits.

Placement (the whole rule):

- ``JAX_COMPILATION_CACHE_DIR`` set by the caller: JAX reads it itself,
  this code sets no other directory, and the agent hands the same value
  to its workers (:func:`resolve_cache_dir` in ``worker_env``).
- unset: one fixed path inside the checkout, :data:`DEFAULT_CACHE_DIR`
  (git-ignored). Never a path made from a temporary name, a pid or the
  time.

Turning the cache off is JAX's own switch too
(``JAX_ENABLE_COMPILATION_CACHE=0``; the chaos harnesses' cold arm sets
it). ``DLROVER_COMPILE_CACHE_MIN_COMPILE_S`` keeps kernel-sized entries
out. :func:`enable_compile_cache` is idempotent and must run before the
first compilation it should serve; a directory that cannot be made
raises — a cache silently off is an unexplained slow restart.
"""

import os
from typing import Optional

from .log import logger

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_ENABLE_ENV = "JAX_ENABLE_COMPILATION_CACHE"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_compile_cache",
)


def resolve_cache_dir() -> str:
    """The directory the placement rule yields in this environment.
    Imports no JAX — safe in the agent and in harness parents."""
    return os.environ.get(CACHE_DIR_ENV) or DEFAULT_CACHE_DIR


def enable_compile_cache(min_compile_s: Optional[float] = None) -> str:
    """Apply the placement rule to this process's jax config and return
    the directory in effect."""
    import jax

    from .config import get_context

    path = os.environ.get(CACHE_DIR_ENV)
    if not path:
        path = DEFAULT_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        if jax.config.jax_compilation_cache_dir != path:
            jax.config.update("jax_compilation_cache_dir", path)
            logger.info("persistent compile cache: %s", path)
    if min_compile_s is None:
        min_compile_s = get_context().compile_cache_min_compile_s
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", float(min_compile_s)
    )
    return path
