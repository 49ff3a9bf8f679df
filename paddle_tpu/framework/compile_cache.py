"""Where JAX's persistent compilation cache lives.

One rule for every entry point (benchmark/run.py, chip_smoke.py, tools/): the
operator places the cache from outside with ``JAX_COMPILATION_CACHE_DIR``
(JAX reads that variable itself); when it is unset the cache sits at a
fixed path inside the checkout. The path is part of the cache key, so a
directory that moves between runs never hits.
"""

from __future__ import annotations

import os

__all__ = ["CHECKOUT_CACHE_DIR", "setup_compile_cache"]

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def setup_compile_cache() -> str:
    """Call before the first compilation. Returns the directory in use.

    ``JAX_COMPILATION_CACHE_DIR`` set: nothing is touched. Unset:
    ``jax_compilation_cache_dir`` becomes ``<checkout>/.jax_cache``
    (gitignored). Thresholds stay at JAX's defaults either way."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
