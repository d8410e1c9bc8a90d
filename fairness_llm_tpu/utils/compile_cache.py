"""Where JAX's persistent compilation cache lives — decided in ONE place.

Every entry point (``cli.main.main``, ``bench.py``, ``chip_smoke.py``, the
``tools/`` scripts) calls :func:`place_compile_cache` before its first
compile. The serving programs of a 7B model take minutes to compile and a
fresh machine keeps nothing between runs, so a warm cache is most of a
second run's set-up time.

The directory is chosen from outside when ``JAX_COMPILATION_CACHE_DIR`` is
set (JAX reads that variable itself; this module then sets nothing), and is
otherwise one fixed, git-ignored directory at the checkout root. It is never
a temporary, per-process or timestamped path: the cache only hits when the
next process looks in the same place.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory. Call before the first compile of the process."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
