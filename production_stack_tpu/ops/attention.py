"""Attention ops.

Two families:

- ``dense_causal_attention``: batched (B, S) causal attention used for
  whole-prompt forward passes, parity tests and the graft entry. Pure XLA —
  the (S, S) masked softmax-matmul fuses onto the MXU.
- paged/ragged attention lives in ``ops/paged_attention.py`` (XLA reference
  path) and ``ops/paged_attention_pallas.py`` (TPU Pallas kernel): the serving
  hot path over the paged KV cache.

All softmax accumulation is float32 regardless of activation dtype.
"""

from __future__ import annotations

import jax.numpy as jnp

NEG_INF = -1e30


def dense_causal_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    scale: float | None = None,
    soft_cap: float = 0.0,
    window: int = 0,
) -> jnp.ndarray:
    """Causal multi-head attention with grouped KV (GQA).

    q: (B, S, H, D); k, v: (B, S, KH, D) with H = KH * G. Returns (B, S, H, D).
    A value may be narrower than its key, (B, S, KH, Dv) (latent attention:
    the rows' latent part); the result is then (B, S, H, Dv).
    ``soft_cap`` > 0 applies Gemma-2-style score capping cap*tanh(s/cap)
    before masking. ``window`` > 0: row t sees rows t - window + 1 .. t.
    """
    B, S, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    scale = scale if scale is not None else D**-0.5

    qg = q.reshape(B, S, KH, G, D)
    scores = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if soft_cap:
        scores = soft_cap * jnp.tanh(scores / soft_cap)
    causal = jnp.tril(jnp.ones((S, S), dtype=bool))
    if window:
        causal &= ~jnp.tril(jnp.ones((S, S), dtype=bool), -window)
    scores = jnp.where(causal[None, None, None], scores, NEG_INF)
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(B, S, H, v.shape[-1]).astype(q.dtype)


def segment_causal_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_positions: jnp.ndarray,
    kv_positions: jnp.ndarray,
    q_segments: jnp.ndarray,
    kv_segments: jnp.ndarray,
    scale: float | None = None,
    soft_cap: float = 0.0,
) -> jnp.ndarray:
    """Ragged attention over flattened token streams.

    Multiple sequences are packed along one token axis; a (q, kv) pair may
    attend iff the tokens share a segment id and kv_pos <= q_pos. Padding uses
    segment id -1. q: (T, H, D); k, v: (Tk, KH, D).
    """
    T, H, D = q.shape
    KH = k.shape[1]
    G = H // KH
    scale = scale if scale is not None else D**-0.5

    qg = q.reshape(T, KH, G, D)
    scores = jnp.einsum(
        "qkgd,skd->kgqs", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if soft_cap:
        scores = soft_cap * jnp.tanh(scores / soft_cap)
    valid = (
        (q_segments[:, None] == kv_segments[None, :])
        & (kv_positions[None, :] <= q_positions[:, None])
        & (q_segments[:, None] >= 0)
    )
    scores = jnp.where(valid[None, None], scores, NEG_INF)
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    denom = probs.sum(axis=-1, keepdims=True)
    probs = probs / jnp.maximum(denom, 1e-30)
    out = jnp.einsum("kgqs,skd->qkgd", probs, v.astype(jnp.float32))
    return out.reshape(T, H, D).astype(q.dtype)
