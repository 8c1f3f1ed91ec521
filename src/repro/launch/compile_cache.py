"""Where JAX's persistent compilation cache lives, decided in one place.

`launch/train.py`, `launch/serve.py` and `chip_smoke.py` call
`use_compile_cache()` before their first compile. The cache key includes
the directory, so it must not move between runs.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the checkout root (src/repro/launch/ -> three levels up)
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    A `JAX_COMPILATION_CACHE_DIR` from the environment is left alone (JAX
    reads it itself); otherwise the cache goes to `<checkout>/.jax_cache`.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
