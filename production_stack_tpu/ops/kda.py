"""Gated delta-rule linear attention with per-channel decay (KDA): what a
recurrent layer keeps per decode slot and how a step moves it.

Per head, with a state ``S`` (d_k, d_v) in float32::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``alpha_t`` in (0, 1) per key channel, ``beta_t`` a scalar in (0, 1), or
(0, 2) where negative eigenvalues are allowed. q, k, v come from a causal
depthwise convolution over time (width ``K``, then SiLU) of their
projections, so a slot also keeps the last ``K - 1`` projected rows: its
conv tail.

Everything here is ``jax.numpy``: the forms the CPU and the tests run, and
the preparation both the XLA and the Pallas recurrences share. The Pallas
kernels (``kda_decode_step``, ``kda_chunk_scan``) are in
``ops/kda_pallas.py``. A step works on what ``prepare`` returns, all
float32, (..., H, D): ``a`` = alpha, ``kb`` = beta k, ``k``, ``q`` (L2
normalised, q scaled by d_k^-1/2), ``vb`` = beta v; with them

    S' = Diag(a) S;  w = vb - S'^T kb;  S_t = S' + k w^T;  o = S_t^T q

The same recurrence over a block of ``C`` rows at once (the chunked form
``kda_chunk_scan`` runs; Kimi Linear's KDA, flash-linear-attention's
``chunk_kda``): with ``G_t = g_1 + ... + g_t`` the running sum of the
log-decay ``g = log a`` inside the block, per key channel, and the rows
of the block stacked into ``Kb, K, Q, Vb`` (C, d),

    A[t, i] = sum_c kb_t[c] k_i[c] exp(G_t[c] - G_i[c])    (i < t)
    B[t, i] = sum_c  q_t[c] k_i[c] exp(G_t[c] - G_i[c])    (i <= t)
    W = (I + A)^-1 (Vb - (exp(G) * Kb) S_0)
    O = (exp(G) * Q) S_0 + B W
    S_C = Diag(exp(G_C)) S_0 + (exp(G_C - G) * K)^T W

row ``t`` of ``W`` being the ``w`` of step ``t``. A decay is never
divided by: ``exp(-G_i)`` leaves float32 after a few strongly decayed
rows (``g`` is unbounded below), so every ratio is formed as
``exp(G_t - G_i)`` with ``t >= i``, which is at most 1, from sums of ``g``
itself (``log a`` of an ``a`` that underflowed would be ``-inf``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def l2norm(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def prepare(q, k, v, g, beta, neg_eigval: bool):
    """Convolved q, k, v (..., H, D), log-decay ``g`` (..., H, D) <= 0 and
    ``beta`` (..., H) in (0, 1) -> (a, kb, k, q, vb), float32."""
    q = l2norm(jax.nn.silu(q.astype(F32))) * (q.shape[-1] ** -0.5)
    k = l2norm(jax.nn.silu(k.astype(F32)))
    v = jax.nn.silu(v.astype(F32))
    beta = beta.astype(F32)[..., None] * (2.0 if neg_eigval else 1.0)
    return jnp.exp(g.astype(F32)), beta * k, k, q, beta * v


def split_heads(qkv: jnp.ndarray, heads: int):
    """A layer's convolved rows (..., 3*H*D) = [q | k | v] -> q, k, v
    (..., H, D)."""
    x = qkv.reshape(*qkv.shape[:-1], 3, heads, -1)
    return x[..., 0, :, :], x[..., 1, :, :], x[..., 2, :, :]


def delta_step(S, a, kb, k, q, vb):
    """One token of one or more heads: S (..., dk, dv), the rest (..., D).
    Returns (S_t, o_t)."""
    S = S * a[..., :, None]
    w = vb - jnp.einsum("...kv,...k->...v", S, kb)
    S = S + k[..., :, None] * w[..., None, :]
    return S, jnp.einsum("...kv,...k->...v", S, q)


# -- the short convolution ----------------------------------------------------

def conv_dense(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Causal depthwise convolution from an empty past: x (B, T, C),
    w (K, C) with w[0] on the current row -> (B, T, C)."""
    out = x * w[0]
    for i in range(1, w.shape[0]):
        out = out + jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, :-i] * w[i]
    return out


def conv_decode(x, w, tail, active):
    """One row a slot: x (S, C), tail (S, K-1, C) oldest first. Returns
    (convolved (S, C), new tail); idle slots keep their tail."""
    K = w.shape[0]
    out = x * w[0]
    for i in range(1, K):
        out = out + tail[:, K - 1 - i] * w[i]
    new = jnp.concatenate([tail[:, 1:], x[:, None]], axis=1)
    return out, jnp.where(active[:, None, None], new, tail)


def stream_spans(cu_q_lens, context_lens, T: int):
    """Of a packed stream with spans in slot order: each row's slot
    (clipped; rows past the last span belong to none: ``live`` False), its
    offset in its span, and per slot the span's length and whether it
    starts its sequence (position 0)."""
    S = context_lens.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)
    slot = jnp.clip(jnp.searchsorted(cu_q_lens, t, side="right")
                    .astype(jnp.int32) - 1, 0, S - 1)
    q_len = cu_q_lens[1:] - cu_q_lens[:-1]
    fresh = (q_len > 0) & (context_lens == q_len)
    return slot, t - cu_q_lens[slot], t < cu_q_lens[S], q_len, fresh


def continues_one_row(q_len, context_lens):
    """Spans of one row that continue a stored state: a ragged stream's
    decode rows. The Pallas path sends them through the decode kernel
    and every other span through the span kernel (``kda_pallas.
    kda_ragged``); the engine counts what each carries by the same rule
    (arrays of either ``numpy`` or ``jax.numpy``)."""
    return (q_len == 1) & (context_lens > 1)


def conv_ragged(x, w, tail, cu_q_lens, context_lens):
    """The packed stream: x (T, C); a span's first rows reach back into
    its slot's tail (zeros where the span starts its sequence). Returns
    (convolved (T, C), new tail (S, K-1, C)); a slot with no span keeps
    its tail.

    The stream's own part is one elementwise pass over shifted copies of
    ``x`` (a row takes the rows of its own span only); what the tails add
    touches a span's first K - 1 rows, (S, K-1) rows in all, and is added
    there. Gathering a tail row for every row of the stream cost six
    passes over the (T, C) rows a layer on the chip (PERF.md section 6)."""
    T, K = x.shape[0], w.shape[0]
    S = tail.shape[0]
    _, off, _, q_len, fresh = stream_spans(cu_q_lens, context_lens, T)
    tail = jnp.where(fresh[:, None, None], jnp.zeros_like(tail), tail)
    out = x * w[0]
    for i in range(1, K):
        shifted = jnp.pad(x, ((i, 0), (0, 0)))[:T]
        out = out + jnp.where((off >= i)[:, None], shifted, 0) * w[i]
    j = jnp.arange(K - 1, dtype=jnp.int32)
    # row o of a span (o < K - 1) lacks taps i > o: tail row K - 1 - i + o
    past = jnp.zeros((S, K - 1, x.shape[1]), jnp.float32)
    for i in range(1, K):
        o = jnp.arange(i)                      # the rows tap i reaches past
        past = past.at[:, o].add(
            (tail[:, K - 1 - i + o] * w[i]).astype(jnp.float32))
    rows = cu_q_lens[:-1, None] + j[None, :]
    inside = j[None, :] < q_len[:, None]
    out = out.at[jnp.where(inside, rows, T)].add(
        past.astype(out.dtype), mode="drop")
    # a slot's last K-1 rows of [tail ; span]
    back = (K - 1 - j)[None, :]                        # rows before the end
    from_span = back <= q_len[:, None]
    src = jnp.clip(cu_q_lens[1:, None] - back, 0, T - 1)
    old = jnp.clip(j[None, :] + q_len[:, None], 0, K - 2)
    new = jnp.where(from_span[..., None], x[src],
                    jnp.take_along_axis(tail, old[..., None], axis=1))
    return out, new


# -- the recurrence, in XLA ---------------------------------------------------

def recurrence_dense(a, kb, k, q, vb):
    """Whole sequences from a zero state, token by token: (B, T, H, D)
    each -> o (B, T, H, D) float32. The definition the other forms are
    held against."""
    B, T, H, D = k.shape

    def step(S, xs):
        S, o = delta_step(S, *xs)
        return S, o

    xs = jax.tree.map(lambda x: jnp.moveaxis(x, 1, 0), (a, kb, k, q, vb))
    _, o = lax.scan(step, jnp.zeros((B, H, D, vb.shape[-1]), F32), xs)
    return jnp.moveaxis(o, 0, 1)


def recurrence_decode(state, layer, a, kb, k, q, vb, active):
    """One token a slot: state (Lk, S, H, dk, dv), the rest (S, H, D).
    Idle slots keep their state. Returns (o (S, H, dv), state)."""
    S0 = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    S1, o = delta_step(S0, a, kb, k, q, vb)
    S1 = jnp.where(active[:, None, None, None], S1, S0)
    return o, lax.dynamic_update_index_in_dim(state, S1, layer, 0)


def recurrence_ragged(state, layer, a, kb, k, q, vb, cu_q_lens,
                      context_lens):
    """The packed stream, row by row: (T, H, D) each. A span starts from
    its slot's state (zeros at position 0) and leaves its last state
    behind; rows past the last span read zero. Returns (o (T, H, dv),
    state)."""
    T = k.shape[0]
    slot, off, live, _, fresh = stream_spans(cu_q_lens, context_lens, T)
    S_all = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)

    def step(S_all, xs):
        s, first, ok, *row = xs
        S0 = lax.dynamic_index_in_dim(S_all, s, 0, keepdims=False)
        S0 = jnp.where(first, jnp.zeros_like(S0), S0)
        S1, o = delta_step(S0, *row)
        S_all = lax.dynamic_update_index_in_dim(
            S_all, jnp.where(ok, S1, S0), s, 0)
        return S_all, jnp.where(ok, o, 0.0)

    first = (off == 0) & fresh[slot] & live
    S_all, o = lax.scan(step, S_all, (slot, first, live, a, kb, k, q, vb))
    return o, lax.dynamic_update_index_in_dim(state, S_all, layer, 0)
