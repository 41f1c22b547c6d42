"""JAX's persistent compilation cache, placed from outside.

Every entry point that compiles for the chip (``chip_smoke.py``,
``service.app.serve()``) calls ``enable_compile_cache()`` before its
first compile. Tests never do.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
module sets no other directory. Otherwise the cache lives at one fixed path
inside the checkout (``<repo>/.jax_cache``, listed in ``.gitignore``): a
directory named after a pid, a temp dir or the time is gone, or another
one, by the next process, so nothing it held would be found again.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: The fallback directory: fixed, inside the checkout.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def compile_cache_dir() -> str:
    """Where the cache lives: the environment's choice, else DEFAULT_DIR."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory. Call before
    the process's first compile."""
    import jax

    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # Cache every executable, not only those that took over a second. At
    # the default (1 s) a chip run of chip_smoke.py kept 2 of its 11
    # programs (the two steps) and recompiled the small accum / broadcast /
    # convert programs on every boot: 2.3 s more per run (PERF.md, PR 21).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return compile_cache_dir()


def cache_entries(path: str | None = None) -> int:
    """Compiled executables in the cache directory (0 when it is absent).
    JAX writes one ``<key>-cache`` file per executable (plus an ``-atime``
    stamp beside it)."""
    try:
        names = os.listdir(path or compile_cache_dir())
    except OSError:
        return 0
    return sum(1 for n in names if n.endswith("-cache"))
