"""The state-space scan with heads (Mamba-2, "SSD"): what a Falcon-H1
layer's state-space mixer keeps per decode slot and how a step moves it.

Per head ``h`` of ``P`` channels, with a state of ``N`` values a channel in
float32 (``S``: (N, P), the channels on the lanes)::

    S_t = a_t * S_{t-1} + B_t (d_t x_t)^T         a_t = exp(d_t A_h)
    y_t = S_t^T C_t + D_h x_t

``a_t`` is ONE scalar a head and token (Mamba-1's decay is per channel and
state value: ops/mamba.py), ``d_t`` = softplus(dt_t + dt_bias_h) > 0, ``A_h``
= -exp(A_log_h) < 0, so every ``a`` lies in (0, 1). ``B_t`` and ``C_t`` (N
each) are shared by the ``H / G`` heads of a group: head h reads group
``h // (H / G)``. ``x``, ``B`` and ``C`` are the layer's projected row after
a causal depthwise convolution over time (width ``K``, with a bias, then
SiLU) over all ``H P + 2 G N`` channels, so a slot also keeps the last
``K - 1`` rows before the convolution: its conv tail, served by
``ops/kda.py`` ``conv_dense`` / ``conv_decode`` / ``conv_ragged``.

The same recurrence over a block of ``C`` rows at once (what
``ssd_chunk_scan`` runs; a scalar decay a head is exactly what lets a block
go through the MXU). With ``g = d A`` the log-decay and ``G_t = g_1 + ... +
g_t`` its running sum inside the block, the block's rows stacked into
``Bm, Cm`` (C, N) and ``X`` (C, P) = d x::

    M[t, i] = (C_t . B_i) exp(G_t - G_i)            (i <= t, else 0)
    Y   = M X + (exp(G) * Cm) S_0
    S_C = exp(G_C) S_0 + (exp(G_C - G) * Bm)^T X

No decay is divided by: every exponent is a sum of ``g`` over rows between
the two, at most 0.

Everything here is ``jax.numpy``: the forms the CPU and the tests run.
Three forms of the scan are held against each other
(tests/test_falcon_h1.py): ``scan_dense`` (whole sequences from a zero
state: the definition), ``scan_decode`` (one row a slot) and
``scan_ragged`` (spans of a packed stream; a span continues its slot's
state, or starts from zeros at position 0). The Pallas kernels
(``ssd_decode_step``, ``ssd_chunk_scan``) are in ``ops/ssd_pallas.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from production_stack_tpu.ops.kda import stream_spans

F32 = jnp.float32


def by_head(v: jnp.ndarray, heads: int) -> jnp.ndarray:
    """B or C (..., G, N) as each head reads it, (..., H, N)."""
    return jnp.repeat(v, heads // v.shape[-2], axis=-2)


def mix(sp: dict, xbc: jnp.ndarray, dt: jnp.ndarray, heads: int,
        groups: int, state_size: int, conv, scan) -> jnp.ndarray:
    """A layer's stateful part, whatever form its state takes: the rows
    before the convolution ``xbc`` (..., H*P + 2*G*N) = [x | B | C] and the
    raw step ``dt`` (..., H) -> ``y`` (..., H*P) float32 with the skip
    term. ``conv(xbc, taps)`` is the causal convolution and ``scan(g, dx,
    B, C)`` the scan over its own state: ``g`` = d A (..., H) the
    log-decay, ``dx`` = d x (..., H, P), ``B`` and ``C`` (..., G, N), all
    float32."""
    xc = jax.nn.silu(conv(xbc, sp["conv"]) + sp["conv_bias"]).astype(F32)
    gn = groups * state_size
    inner, lead = xc.shape[-1] - 2 * gn, xc.shape[:-1]
    x = xc[..., :inner].reshape(*lead, heads, -1)
    B = xc[..., inner:inner + gn].reshape(*lead, groups, state_size)
    C = xc[..., inner + gn:].reshape(*lead, groups, state_size)
    d = jax.nn.softplus(dt.astype(F32) + sp["dt_bias"].astype(F32))
    g = -jnp.exp(sp["a_log"].astype(F32)) * d
    y = scan(g, d[..., None] * x, B, C)
    y = y + sp["d"].astype(F32)[:, None] * x
    return y.reshape(*lead, inner)


def scan_step(S, g, dx, B, C):
    """One token: S (..., H, N, P), g (..., H), dx (..., H, P), B and C
    (..., G, N), all float32. Returns (S_t, y_t (..., H, P)) without the
    skip term ``D x``."""
    heads = S.shape[-3]
    S = (jnp.exp(g)[..., None, None] * S
         + by_head(B, heads)[..., :, None] * dx[..., None, :])
    return S, jnp.einsum("...hnp,...hn->...hp", S, by_head(C, heads))


def scan_dense(g, dx, B, C):
    """Whole sequences from a zero state, token by token: g (Bt, T, H), dx
    (Bt, T, H, P), B, C (Bt, T, G, N) -> y (Bt, T, H, P) float32. The
    definition the other forms are held against."""
    def step(S, row):
        return scan_step(S, *row)

    rows = jax.tree.map(lambda a: jnp.moveaxis(a.astype(F32), 1, 0),
                        (g, dx, B, C))
    Bt, _, H, P = dx.shape
    S0 = jnp.zeros((Bt, H, B.shape[-1], P), F32)
    return jnp.moveaxis(lax.scan(step, S0, rows)[1], 0, 1)


def scan_decode(state, layer, g, dx, B, C, active):
    """One token a slot: state (L, S, H, N, P), g (S, H), dx (S, H, P), B
    and C (S, G, N). Idle slots keep their state. Returns (y (S, H, P),
    state)."""
    S0 = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    S1, y = scan_step(S0, g, dx, B, C)
    S1 = jnp.where(active[:, None, None, None], S1, S0)
    return y, lax.dynamic_update_index_in_dim(state, S1, layer, 0)


def scan_ragged(state, layer, g, dx, B, C, cu_q_lens, context_lens):
    """The packed stream, row by row: g (T, H), dx (T, H, P), B, C (T, G,
    N). A span starts from its slot's state (zeros at position 0) and
    leaves its last state behind; rows past the last span read zero.
    Returns (y (T, H, P), state)."""
    T = dx.shape[0]
    slot, off, live, _, fresh = stream_spans(cu_q_lens, context_lens, T)
    S_all = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)

    def step(S_all, xs):
        s, first, ok, *row = xs
        S0 = lax.dynamic_index_in_dim(S_all, s, 0, keepdims=False)
        S0 = jnp.where(first, jnp.zeros_like(S0), S0)
        S1, y = scan_step(S0, *row)
        S_all = lax.dynamic_update_index_in_dim(
            S_all, jnp.where(ok, S1, S0), s, 0)
        return S_all, jnp.where(ok, y, 0.0)

    first = (off == 0) & fresh[slot] & live
    S_all, y = lax.scan(step, S_all, (slot, first, live, g, dx, B, C))
    return y, lax.dynamic_update_index_in_dim(state, S_all, layer, 0)
