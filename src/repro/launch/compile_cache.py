"""Where JAX keeps its persistent compilation cache for this repo's entry
points (``launch/serve.py``, ``launch/train.py``, ``benchmarks/run.py``,
``chip_smoke.py``).

``JAX_COMPILATION_CACHE_DIR``, when set, is used as is: JAX reads it
itself and nothing here overrides it.  Otherwise the cache lives at the
fixed ``<repo>/.jax_cache`` (git-ignored).  The directory must not move
between runs — a temporary name, a PID or a time in the path would make
every run miss.  Called from the entry points' ``main``, never at import.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
