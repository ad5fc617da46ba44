"""JAX configuration shared by all device-path modules.

The math plane works in int64 (moduli up to 61 bits); JAX defaults to 32-bit,
so every module that touches jax calls ``ensure_x64()`` before tracing. The
same once-per-process initialisation places the persistent compilation
cache and puts telemetry's listeners on JAX's own trace / lower / compile /
cache-load events (``telemetry/jaxevents.py``), so no entry point can forget
either: what a program costs before its first dispatch is counted from here on.
"""

from __future__ import annotations

import os
import pathlib

import numpy as np

#: use in Pallas BlockSpec index maps instead of a literal ``0``: under
#: jax_enable_x64 (the package default) a Python-int index traces as i64,
#: which Mosaic's TPU compile rejects — witnessed on v5e 2026-07-31
I32_ZERO = np.int32(0)

#: where compiled programs persist when the caller names no other place.
#: Fixed inside the checkout (the path is part of the cache key, so a
#: directory that moves never hits) and listed in .gitignore.
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"

_done = False


def ensure_x64() -> None:
    global _done
    if _done:
        return
    import jax

    from ..telemetry import jaxevents

    jax.config.update("jax_enable_x64", True)
    _place_compilation_cache()
    jaxevents.register()
    _done = True


def _place_compilation_cache() -> None:
    """``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, touch
    nothing. Unset: the one fixed in-checkout directory. The only place
    in the code that sets a cache path."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
