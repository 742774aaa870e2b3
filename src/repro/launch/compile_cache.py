"""JAX's persistent compilation cache for the entry points.

The launcher's ``main()`` and ``chip_smoke.py`` call :func:`enable` before
they compile anything; importing :mod:`repro` never does, and tests do not
call it.  The cache directory is part of each entry's key, so it has to be
a fixed path: ``JAX_COMPILATION_CACHE_DIR`` where the environment sets it
(JAX reads that variable itself, and :func:`enable` then sets no other
directory), otherwise ``.jax_cache/`` at the root of the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT_ROOT / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Every program is cached, not only those past JAX's default one-second
    compile time: a run from a cold process pays for all of them."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
