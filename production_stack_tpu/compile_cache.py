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
_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_compile_cache",
)


def configure_compile_cache() -> str:
    """Point jax at the persistent compile cache; return its directory."""
    placed = os.environ.get(_ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", _DEFAULT)
    return _DEFAULT
