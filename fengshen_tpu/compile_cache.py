"""Where XLA's persistent compilation cache lives.

A cold start at 13B width spends minutes compiling; jax can keep the
compiled programs on disk and load them on the next start, but only if
every start looks in the same place — the directory is part of what a
cache entry is keyed by, so a name that moves (a `tempfile` name, a
pid, a timestamp) never hits.

`JAX_COMPILATION_CACHE_DIR` is the caller's way to place the cache (a
job scheduler, a machine image, a test harness); jax reads it itself
and this module then touches nothing. Without it the cache goes to one
fixed, git-ignored directory inside the checkout.
"""

from __future__ import annotations

import os

import jax

#: environment variable jax itself reads for the cache directory
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the one in-checkout location, used when the environment names none
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_compile_cache")


def ensure_compile_cache() -> str:
    """Make sure the persistent compilation cache has a directory and
    return it. Called before the first compile by `Trainer.__init__`,
    `create_continuous_engine` and `chip_smoke.py`; calling it again is
    a no-op."""
    from_env = os.environ.get(CACHE_DIR_ENV)
    if from_env:
        return from_env
    if jax.config.jax_compilation_cache_dir != DEFAULT_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
