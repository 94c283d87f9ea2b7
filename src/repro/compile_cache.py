"""JAX's persistent compilation cache for the repo's entry points.

Called once by a program's ``main`` (``chip_smoke.py``,
``benchmarks/run.py``) before anything compiles; never at import and
never from tests.  A run that finds its programs in the cache skips their
compilation, which on a chip can be a large part of a cold run.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <repo>/.jax_cache (listed in .gitignore).  The directory is part of the
# cache's key, so it is a fixed path: never a temporary name, a process
# id or the time.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> None:
    """Turn the persistent cache on.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to ``REPO_CACHE_DIR``
    and keeps every program, however quickly it compiled (JAX's default
    skips those under a second, which on a CPU is most of them).
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
