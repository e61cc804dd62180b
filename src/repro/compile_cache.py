"""JAX's persistent compilation cache, placed from outside.

Compiling a bucket program or a fused search chunk for the TPU takes tens
of seconds, and every new process starts with no compiled code.  The
entry points (``chip_smoke.py``, ``python -m benchmarks.run``,
``python -m repro.launch.hillclimb``) call :func:`enable_compile_cache`
before they compile anything, so a second run finds its programs on disk.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory.  Otherwise the cache lives at one fixed
path in the checkout, ``<repo>/.jax_cache`` (listed in ``.gitignore``):
the path is part of what makes an entry findable again, so it never
depends on a temporary name, a process id or the time.  The tests leave
the cache off.
"""
from __future__ import annotations

import os
import pathlib

#: the checkout this package runs from (``src/repro`` -> the repo root)
REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn JAX's persistent compilation cache on; returns its directory.

    Returns None (and leaves the cache off) when the variable is unset
    and the package does not run from a checkout, since there is then no
    fixed path of the repo's own to write to."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        if not (REPO_ROOT / "pyproject.toml").is_file():
            return None
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
