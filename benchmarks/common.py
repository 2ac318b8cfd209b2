"""Benchmark utilities: timing with block_until_ready + CSV emission."""
from __future__ import annotations

import os
import time
from pathlib import Path

import jax

ROWS: list[tuple] = []

# JAX's persistent compile cache when JAX_COMPILATION_CACHE_DIR is unset: a
# fixed directory of the checkout (git ignores it).  The path is part of the
# cache key, so it must not move between runs.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parent.parent / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    other path is set here.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def time_fn(fn, *args, warmup=1, repeats=3, **kw):
    """Median wall time (s) of fn(*args) with jitted-result sync."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kw))
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kw))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def emit(name: str, seconds: float, derived: str = ""):
    """Print one ``name,us_per_call,derived`` CSV row (scaffold contract)."""
    row = (name, seconds * 1e6, derived)
    ROWS.append(row)
    print(f"{name},{seconds * 1e6:.1f},{derived}")
