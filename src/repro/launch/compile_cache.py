"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``launch/train.py``, ``launch/simulate.py``)
call :func:`enable_compile_cache` once, before their first compile; nothing
calls it at import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads its cache directory from it and this helper sets no other.  Otherwise
the cache lives at ``.jax_cache/`` in the checkout root: a fixed path, so a
later run of the same checkout finds what an earlier one compiled (the path
is part of what the cache is keyed on, and a moving directory never hits).
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: checkout root (src/repro/launch/ → three levels up)
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
