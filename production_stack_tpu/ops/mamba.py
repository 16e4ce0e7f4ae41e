"""The selective state-space scan (Mamba-1): what a state-space layer keeps
per decode slot and how a step moves it.

Per channel ``c`` of the inner width ``d_i``, with a state of ``N`` values
in float32 (``S``: (N, d_i), the channels on the lanes)::

    S_t = exp(Delta_t A) * S_{t-1} + (Delta_t x_t) B_t^T
    y_t = S_t C_t + D x_t

``x`` is the layer's input after a causal depthwise convolution over time
(width ``K``, with a bias, then SiLU), so a slot also keeps the last
``K - 1`` rows before the convolution: its conv tail, the same operation
as a KDA layer's and served by the same functions (``ops/kda.py``
``conv_dense`` / ``conv_decode`` / ``conv_ragged``). ``Delta`` (per
channel, > 0), ``B`` and ``C`` (N each) are projections of the convolved
row (``selective_inputs``); ``A`` = -exp(A_log) < 0, (N, d_i), so every
factor ``exp(Delta A)`` lies in (0, 1) and a state can neither grow nor
change sign by the decay.

Everything here is ``jax.numpy``: the forms the CPU and the tests run.
Three forms of the scan are held against each other
(tests/test_phi4_flash.py): ``scan_dense`` (whole sequences from a zero
state: the definition), ``scan_decode`` (one row a slot) and
``scan_ragged`` (spans of a packed stream; a span continues its slot's
state, or starts from zeros at position 0). The Pallas kernels
(``mamba_decode_step``, ``mamba_chunk_scan``) are in
``ops/mamba_pallas.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from production_stack_tpu.ops.kda import stream_spans

F32 = jnp.float32


def selective_inputs(mp: dict, xc: jnp.ndarray, state_size: int):
    """A layer's convolved rows ``xc`` (..., d_i) -> (Delta (..., d_i),
    B (..., N), C (..., N)), float32: ``[r; B; C] = W_x xc``, ``Delta =
    softplus(W_dt r + b_dt)``. The matmuls take the model dtype and
    accumulate in float32."""
    rbc = jnp.einsum("...i,ir->...r", xc, mp["w_x"],
                     preferred_element_type=F32)
    rank = rbc.shape[-1] - 2 * state_size
    dt = jnp.einsum("...r,ri->...i", rbc[..., :rank].astype(xc.dtype),
                    mp["w_dt"], preferred_element_type=F32)
    delta = jax.nn.softplus(dt + mp["dt_bias"].astype(F32))
    return delta, rbc[..., rank:rank + state_size], rbc[..., rank + state_size:]


def mix(mp: dict, xs: jnp.ndarray, state_size: int, conv, scan):
    """A layer's stateful part, whatever form its state takes: the rows
    before the convolution ``xs`` (..., d_i) -> ``y`` (..., d_i) float32
    with the skip term. ``conv(xs, taps)`` is the causal convolution and
    ``scan(A, x, delta, B, C)`` the scan (all float32; ``A`` = -exp(A_log),
    (N, d_i)), each over its own state."""
    xc = jax.nn.silu(conv(xs, mp["conv"]) + mp["conv_bias"])
    delta, B, C = selective_inputs(mp, xc, state_size)
    xf = xc.astype(F32)
    y = scan(-jnp.exp(mp["a_log"].astype(F32)), xf, delta, B, C)
    return y + mp["d"].astype(F32) * xf


def scan_step(S, A, x, delta, B, C):
    """One token: S (..., N, d_i), A (N, d_i), x and delta (..., d_i), B
    and C (..., N), all float32. Returns (S_t, y_t (..., d_i)) without the
    skip term ``D x``."""
    S = (jnp.exp(delta[..., None, :] * A) * S
         + (delta * x)[..., None, :] * B[..., :, None])
    return S, jnp.sum(S * C[..., :, None], axis=-2)


def scan_dense(A, x, delta, B, C):
    """Whole sequences from a zero state, token by token: x, delta
    (Bt, T, d_i), B, C (Bt, T, N) -> y (Bt, T, d_i) float32. The
    definition the other forms are held against."""
    def step(S, row):
        return scan_step(S, A, *row)

    rows = jax.tree.map(lambda a: jnp.moveaxis(a.astype(F32), 1, 0),
                        (x, delta, B, C))
    S0 = jnp.zeros((x.shape[0], *A.shape), F32)
    return jnp.moveaxis(lax.scan(step, S0, rows)[1], 0, 1)


def scan_decode(state, layer, A, x, delta, B, C, active):
    """One token a slot: state (Lm, S, N, d_i), x and delta (S, d_i), B
    and C (S, N). Idle slots keep their state. Returns (y (S, d_i),
    state)."""
    S0 = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    S1, y = scan_step(S0, A, x, delta, B, C)
    S1 = jnp.where(active[:, None, None], S1, S0)
    return y, lax.dynamic_update_index_in_dim(state, S1, layer, 0)


def scan_ragged(state, layer, A, x, delta, B, C, cu_q_lens, context_lens):
    """The packed stream, row by row: x, delta (T, d_i), B, C (T, N). A
    span starts from its slot's state (zeros at position 0) and leaves its
    last state behind; rows past the last span read zero. Returns
    (y (T, d_i), state)."""
    T = x.shape[0]
    slot, off, live, _, fresh = stream_spans(cu_q_lens, context_lens, T)
    S_all = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)

    def step(S_all, xs):
        s, first, ok, *row = xs
        S0 = lax.dynamic_index_in_dim(S_all, s, 0, keepdims=False)
        S0 = jnp.where(first, jnp.zeros_like(S0), S0)
        S1, y = scan_step(S0, A, *row)
        S_all = lax.dynamic_update_index_in_dim(
            S_all, jnp.where(ok, S1, S0), s, 0)
        return S_all, jnp.where(ok, y, 0.0)

    first = (off == 0) & fresh[slot] & live
    S_all, y = lax.scan(step, S_all, (slot, first, live, x, delta, B, C))
    return y, lax.dynamic_update_index_in_dim(state, S_all, layer, 0)
