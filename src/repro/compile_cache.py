"""Where JAX keeps its persistent compilation cache for this repo's programs.

The entry points (``chip_smoke.py``, ``repro.launch.serve.main``,
``repro.launch.train.main``, ``benchmarks/run.py``) call
:func:`use_persistent_cache` first thing.  Library code and tests never
do: importing a module changes no JAX setting.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset: fixed
#: and inside the checkout (git ignores it), because the path is part of
#: every cache key — a directory that moves between runs never hits
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is changed here; otherwise the cache goes to
    ``DEFAULT_CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
