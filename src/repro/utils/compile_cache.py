"""Where jax keeps compiled programs between runs of the device scripts.

``chip_smoke.py`` and ``benchmarks/compile_throughput.py`` call
:func:`enable_compile_cache` before their first compile.  A set
``JAX_COMPILATION_CACHE_DIR`` wins: jax reads the variable itself and this
module sets no other directory.  Otherwise the cache lives at
``.jax_cache/`` in the checkout, a fixed path, so a later run of the same
checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    Every program is cached, however fast it compiled: the Pallas kernels
    of the search compile in well under jax's default one-second floor,
    and a cold device run pays for each of them again."""
    import jax

    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
