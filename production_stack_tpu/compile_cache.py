"""Where XLA's persistent compile cache lives.

Engine warm-up compiles every serving program at full depth before
``/ready`` turns 200; a persistent cache turns a restart's warm-up from
compile-bound into load-bound. Every entry point that opens a device
(``engine/server.py`` ``main()``, the kernel check in ``chip_smoke.py``,
the reference child of ``chipbench/``) calls ``configure_compile_cache()`` before its first
backend use, so all of one command's processes share one directory.

The directory is part of the cache key, so it never moves between runs:
``JAX_COMPILATION_CACHE_DIR`` when the operator set it (jax reads that
variable itself — nothing is overridden in code), else one fixed,
git-ignored directory at the root of the checkout.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_MIN_SECS_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_compile_cache",
)


def configure_compile_cache() -> str:
    """Point jax at the persistent compile cache; return its directory.
    Every program is kept there, however quickly it compiled: jax's own
    threshold keeps only those that took a second, and a step program that
    compiles in less (Ouro's do, since nothing in them copies a weight
    stack: 22 signatures of ~0.7 s, PR 53) was compiled again by every
    start, ~10 s of warm-up and probe. The operator's
    ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` holds where it is set."""
    import jax

    if _MIN_SECS_ENV not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    placed = os.environ.get(_ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", _DEFAULT)
    return _DEFAULT
