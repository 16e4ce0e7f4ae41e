"""Gated DeltaNet (GDN): the gated delta rule with ONE scalar decay a head
and token, and a state that need not be square. What a GDN layer keeps per
decode slot and how a step moves it.

Per head, with a state ``S`` (d_k, d_v) in float32 (Olmo-Hybrid: 96 x 192)::

    S_t = (I - beta_t k_t k_t^T) alpha_t S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``alpha_t`` = exp(g_t) in (0, 1), one number a head (KDA's is a vector over
the key channels: ops/kda.py), ``beta_t`` a scalar in (0, 1), or (0, 2)
where negative eigenvalues are allowed. q, k, v come from a causal
depthwise convolution over time (width ``K``, then SiLU) of their
projections, [q | k | v] = 2 H d_k + H d_v channels, so a slot also keeps
the last ``K - 1`` projected rows: its conv tail.

This file is thin over ``ops/kda.py``, whose ``jax.numpy`` forms already
take ``d_k != d_v`` and a decay that broadcasts: ``prepare`` (SiLU, L2
norm, the scale of q, beta; handed ``g`` as (..., H, 1)), ``delta_step``,
the three convolutions, ``stream_spans`` and ``continues_one_row`` are
KDA's own, and the three recurrences below are KDA's over this state's
layout. What differs is that layout and the block form the span kernel
runs (``ops/gdn_pallas.py``).

**The layout.** Neither 96 nor 192 is a multiple of the 128 lanes of a
tile, so (..., H, 96, 192) float32 would pad every head's state to 256
lanes in HBM and in VMEM: a third more bytes in the largest operand of a
decode step. Two heads side by side are 384 lanes, three whole tiles, so
the state lies (layers, slots, H / 2, d_k, 2 d_v): pair ``p`` holds head
``2 p`` in its first ``d_v`` lanes and head ``2 p + 1`` in the rest
(``pack`` / ``unpack``). Nothing of the arithmetic depends on it.

**The block form** (what ``gdn_chunk_scan`` runs). With ``G_t = g_1 + ... +
g_t`` the running sum of the log-decay inside a block of ``C`` rows, the
decays of its pairs are ONE (C, C) matrix ``Gamma[t, i] = exp(G_t - G_i)``
(t >= i; at most 1 by construction, nothing is divided by), and with the
rows stacked into ``Kb = beta K``, ``K``, ``Q`` (C, d_k) and ``Vb = beta V``
(C, d_v) the block is plain matrix products:

    A = tril(Kb K^T * Gamma, -1)        B = tril(Q K^T * Gamma)
    W = (I + A)^-1 (Vb - exp(G) Kb S_0)
    O = exp(G) Q S_0 + B W
    S_C = exp(G_C) S_0 + (exp(G_C - G) K)^T W

row ``t`` of ``W`` being the ``w`` of step ``t``. KDA's per-channel decay
makes ``A`` a sum over channels of products that no single matmul gives
(ops/kda_pallas.py pairs rows off by lane reductions inside a sub-block);
here it is one.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from production_stack_tpu.ops import kda

F32 = jnp.float32


def pack(S: jnp.ndarray) -> jnp.ndarray:
    """(..., H, d_k, d_v) -> (..., H / 2, d_k, 2 d_v): two heads side by
    side on the lanes."""
    *lead, H, dk, dv = S.shape
    S = S.reshape(*lead, H // 2, 2, dk, dv)
    return jnp.moveaxis(S, -3, -2).reshape(*lead, H // 2, dk, 2 * dv)


def unpack(S: jnp.ndarray) -> jnp.ndarray:
    """(..., H / 2, d_k, 2 d_v) -> (..., H, d_k, d_v)."""
    *lead, P, dk, dv2 = S.shape
    S = S.reshape(*lead, P, dk, 2, dv2 // 2)
    return jnp.moveaxis(S, -2, -3).reshape(*lead, 2 * P, dk, dv2 // 2)


def split_heads(qkv: jnp.ndarray, heads: int, key_dim: int):
    """A layer's convolved rows (..., 2 H d_k + H d_v) = [q | k | v] ->
    q, k (..., H, d_k) and v (..., H, d_v)."""
    n = heads * key_dim
    lead = qkv.shape[:-1]
    return (qkv[..., :n].reshape(*lead, heads, key_dim),
            qkv[..., n:2 * n].reshape(*lead, heads, key_dim),
            qkv[..., 2 * n:].reshape(*lead, heads, -1))


# whole sequences from a zero state, (B, T, H, .) each: KDA's, which keeps
# no state of its own shape
recurrence_dense = kda.recurrence_dense


def _on_heads(recurrence, state, layer, *rest):
    """KDA's cached recurrence over layer ``layer`` of a packed state
    (Lg, S, H / 2, d_k, 2 d_v)."""
    S0 = unpack(lax.dynamic_index_in_dim(state, layer, 0, keepdims=False))
    o, S1 = recurrence(S0[None], 0, *rest)
    return o, lax.dynamic_update_index_in_dim(state, pack(S1[0]), layer, 0)


def recurrence_decode(state, layer, a, kb, k, q, vb, active):
    """One token a slot: a (S, H, 1), kb, k, q (S, H, d_k), vb (S, H,
    d_v). Idle slots keep their state. Returns (o (S, H, d_v), state)."""
    return _on_heads(kda.recurrence_decode, state, layer, a, kb, k, q, vb,
                     active)


def recurrence_ragged(state, layer, a, kb, k, q, vb, cu_q_lens,
                      context_lens):
    """The packed stream, row by row: (T, H, .) each. A span starts from
    its slot's state (zeros at position 0) and leaves its last state
    behind. Returns (o (T, H, d_v), state)."""
    return _on_heads(kda.recurrence_ragged, state, layer, a, kb, k, q, vb,
                     cu_q_lens, context_lens)
