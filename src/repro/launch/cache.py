"""Where JAX keeps its persistent compilation cache.

Every entry point (launch/train.py, launch/serve.py, benchmarks/run.py,
chip_smoke.py) calls `use_compile_cache()` before its first compile, so a
second run of the same program on the same chip loads the executables the
first one wrote instead of compiling cold.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a FIXED path: the cache key includes nothing of the directory, but a
# directory that moves (a temp name, a pid, a time) is never found again
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    A `JAX_COMPILATION_CACHE_DIR` set from outside wins: JAX reads it
    itself, and this leaves it alone. Otherwise the cache lives in
    `<repo>/.jax_cache` (listed in .gitignore)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
