"""Where JAX's persistent compilation cache lives.

The cache is placed from outside: where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX itself reads it and this package sets no directory in code.
Where it is not, the cache goes to ONE fixed path inside the checkout —
the path is part of the cache key, so a temporary, pid- or time-derived
directory would never hit.  Worker actors receive the resolved directory
as ``JAX_COMPILATION_CACHE_DIR`` before their first jax import
(``TpuStrategy.env_per_worker``).
"""

from __future__ import annotations

import os

__all__ = ["DEFAULT_CACHE_DIR", "compile_cache_dir", "enable_compile_cache"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".jax_cache",
)


def compile_cache_dir() -> str:
    """The directory this process's (and its workers') compiles cache to."""
    return os.environ.get(_ENV) or DEFAULT_CACHE_DIR


def enable_compile_cache() -> None:
    """Point an already-imported jax at the fixed in-checkout path —
    only where nothing outside placed the cache (the environment
    variable, or a directory the caller configured on ``jax.config``)."""
    import jax

    if os.environ.get(_ENV) or jax.config.jax_compilation_cache_dir:
        return
    from jax.experimental.compilation_cache import compilation_cache as cc

    # jax memoizes "no cache" at the process's first compile; reset so
    # the directory takes effect for the ones that follow.
    cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
