"""JAX's persistent compilation cache, placed for the entry points.

Entry points (`launch/serve.py`, `launch/replica.py`, `benchmarks/run.py`,
`chip_smoke.py`) call `enable_compile_cache()` before their first compile;
library code never does. The cache lives where `JAX_COMPILATION_CACHE_DIR`
says when it is set, and otherwise at `<checkout>/.jax_cache/`. The path is
part of every cache key's lookup, so it is fixed: never built from a temp
name, a pid or the time.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    `$JAX_COMPILATION_CACHE_DIR`, else at the checkout's `.jax_cache/`;
    returns the directory."""
    import jax

    path = os.environ.get(ENV) or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
