"""Where JAX keeps its persistent compilation cache for this checkout.

Scripts (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``) call
:func:`use_compile_cache` once at start-up; importing :mod:`repro` never
does, so the tests write no cache.
"""
from __future__ import annotations

import os
import pathlib

#: The default cache directory: fixed, because the path is part of what a
#: later run must find again.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``.  Call before the first compilation.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
