"""Persistent XLA compile cache at a path that stays put.

The engine compiles a program set per declared tensor and the flagship
model takes minutes to compile, so entry points that run on the chip
(``chip_smoke.py``, ``bench.py --inner``, the ``example/jax`` scripts)
call :func:`enable_compile_cache` before their first compile.
``bps.init()`` does not: a library import must not start writing files.

The directory is part of the cache key (JAX folds its XLA side-cache
paths into the compile options it hashes), so it must be the same on
every run: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it,
otherwise ``<checkout>/.jax_cache`` resolved from this file's location —
never the cwd, a temp name, a pid or a timestamp.
"""

from __future__ import annotations

import os

import jax

from ..common.telemetry import listen_to_compiles

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in use.

    JAX reads ``JAX_COMPILATION_CACHE_DIR`` into
    ``jax_compilation_cache_dir`` by itself; when that (or an earlier
    call) already set a directory, none is set here.  The minimum
    compile time is lowered to zero so the engine's many sub-second
    chunk programs are cached along with the big train step.  Both
    ``jax.jit`` and the engine's ``.lower().compile()`` warm go through
    the same ``compile_or_get_cached``, so one directory serves both.
    What the cache then does for a job — hits, misses, the seconds spent
    loading against compiling — is counted from here on
    (``compile.*``, ``common/telemetry.py`` ``listen_to_compiles``).
    """
    listen_to_compiles()
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
