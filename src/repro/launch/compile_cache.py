"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so the directory has to stay put from
one run to the next: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets
it (JAX reads the variable itself), otherwise ``.jax_cache`` at the root of
the checkout, which ``.gitignore`` lists.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's compilation cache at its fixed directory; returns it.

    Call before the first compilation of the process.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
