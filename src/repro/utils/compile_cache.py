"""JAX's persistent compilation cache, placed from outside the program.

Entry points (`launch.train.main`, `launch.serve.main`, `benchmarks/run.py`,
`chip_smoke.py`) call `use_compile_cache()` before they compile anything;
imports and tests never do. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX
reads it by itself and nothing here overrides it. Otherwise the cache is
the fixed `<checkout>/.jax_cache` (git-ignored): its path is part of what
the cache is keyed on, so it never takes a temporary, per-process or
per-run name.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Every program is cached, not only those that took JAX's default one
    second to compile: a round step of the paper's problems compiles in
    less, and a fresh process compiles dozens of them."""
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
