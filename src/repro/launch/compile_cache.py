"""JAX's persistent compilation cache, placed from outside the program.

Every entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``) calls :func:`use_compile_cache` before its first
compile, so a second run of the same programs loads them instead of
recompiling (a cold full-width qwen1.5-0.5b serve compiles one prefill
program per prompt length plus the decode step and the kernels).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set in code. Otherwise the cache lives at the fixed path
``<repo>/.jax_cache`` (listed in ``.gitignore``). The path never
depends on a temp dir, a pid or the time: a cache that moves between
runs is never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
