"""Where compiled programs are kept between runs.

A cold BERT-Large step compiles for the better part of a minute and the
serving engine AOT-compiles four to six programs; JAX's persistent
compilation cache pays that once per checkout.  The entry points that run
on the chip (``chip_smoke.py``, ``bench.py``, the examples,
``tools/serve_bench.py``) call :func:`enable_compile_cache` first thing;
``import apex_tpu`` and the test suite never do.

The directory is part of the cache key, so it must not move between runs:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets no directory;
- otherwise ``<checkout>/.jax_cache``, derived from this file's own
  location — never a temp dir, a pid or the clock.

CPU runs (tests, tiny-size debugging) stay out: their executables are of
no use on the chip and would only swell the tree the chip tool copies.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

__all__ = ["compile_cache_dir", "enable_compile_cache"]

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def compile_cache_dir() -> str:
    """The directory the persistent cache lives in for this checkout."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, ".jax_cache"
    )


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on; returns the directory
    in use, or None on the CPU backend (no cache)."""
    from_env = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    if not from_env and jax.default_backend() == "cpu":
        return None
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    # keep the serve programs too (1-4 s each; the default floor is 1 s)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return compile_cache_dir()
