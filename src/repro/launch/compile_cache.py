"""Where the persistent JAX compilation cache lives.

The entry points (``chip_smoke.py``, :mod:`repro.launch.serve`,
``benchmarks/run.py``, :mod:`repro.serve.faults`) call
:func:`enable_compile_cache` once, before they compile anything; no
library module turns the cache on when it is imported.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the fixed cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: ``<checkout>/.jax_cache`` (this file is ``src/repro/launch/…``).  It
#: never moves between runs, so a later run of the same checkout hits.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads
    it itself; otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
