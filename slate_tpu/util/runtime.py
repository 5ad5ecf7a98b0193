"""Process-level runtime choices: how Pallas kernels run on this backend,
and where entry scripts keep JAX's persistent compilation cache.

Importing the library sets nothing; the entry scripts (chip_smoke.py,
``python -m slate_tpu.tune``) call :func:`use_compile_cache`.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[2]


def pallas_interpret() -> bool:
    """Pallas kernels compile natively on a TPU and run in interpret mode
    on any other backend (the CPU tests).  Nothing is caught: a process
    whose backend cannot start fails here, not in a silent interpreter."""
    return jax.default_backend() != "tpu"


def use_compile_cache() -> str:
    """Keep compiled programs in JAX's persistent cache and return its
    directory: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it
    itself), else the fixed, gitignored ``.jax_cache/`` at the checkout
    root — a path that never moves, so a later run finds its entries."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
