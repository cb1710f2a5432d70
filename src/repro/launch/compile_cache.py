"""JAX's persistent compilation cache for this repository's entry points.

Called from the ``__main__`` block of ``chip_smoke.py``, ``launch/serve.py``
and ``bench/run.py``, never on import, so a library user or a test process
keeps whatever cache configuration it has.
"""

from __future__ import annotations

import os
import pathlib

import jax

# a fixed path inside the checkout: the cache directory is part of what a
# later run must find again, so it never carries a pid, a timestamp or a
# temporary name
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets no other directory; otherwise the cache lives at
    ``<repo>/.jax_cache``.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # keep every program: a cold run on a TPU v5e compiles hundreds of small
    # eager programs at ~0.25 s each, which JAX's default 1 s floor never keeps
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
