"""A family's published scalars (Falcon-H1's multipliers): applied on the
served path as the published code applies them, and divided out of the
random stand-in weights they multiply. A scalar of 1, every other
family's, changes neither a program nor a weight."""

from __future__ import annotations

import jax.numpy as jnp


def times(x: jnp.ndarray, scalar: float) -> jnp.ndarray:
    """``x * scalar`` in ``x``'s own type, as the published code multiplies;
    a scalar of 1 adds nothing to the program."""
    return x if scalar == 1 else x * jnp.asarray(scalar, x.dtype)


def over(w: jnp.ndarray, scalar) -> jnp.ndarray:
    """``w`` / ``scalar`` (a number, or one a column): a stand-in matrix
    drawn at its usual size OVER the scalar that multiplies its output or
    input (models/falcon_h1.py's one rule); a scalar of 1 leaves ``w`` as
    it was drawn."""
    if isinstance(scalar, (int, float)) and scalar == 1:
        return w
    return (w.astype(jnp.float32)
            / jnp.asarray(scalar, jnp.float32)).astype(w.dtype)
