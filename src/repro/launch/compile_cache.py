"""Where JAX keeps its persistent compilation cache.

A cold process compiles every program it runs; on a TPU that is minutes for
a full-width training step.  The persistent cache keys entries on, among
other things, the directory it lives in, so the directory must be fixed: a
path built from a temporary name, a process id or the time never hits.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins; the
code then sets no other path.  Otherwise the cache lives in ``.jax_cache`` at
the root of the checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its fixed directory.

    Idempotent; call before the first compile.  Returns the directory in
    use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
