"""Paged attention over the block-table KV cache — XLA reference path.

Cache layout (single fused buffer): ``(L, N, block_size, 2*KH, D)``.

Why this layout:
- ONE buffer + ONE scatter per layer keeps the donated pool aliased through
  the scan carry (two carried buffers, or two scatters, cost a full pool
  copy per step);
- a token's K+V for all heads is one contiguous ``(2*KH, D)`` slab — the
  exact bf16 (16, 128) tile at KH=8, a quarter tile per shard at TP=4
  (padded to a whole tile in VMEM, not in HBM) — so Pallas writes/reads
  slice only leading dims and one DMA moves K and V together;
- the head dim is grouped per tensor-parallel shard: ``[K_shard0, V_shard0,
  K_shard1, V_shard1, ...]`` so a NamedSharding split over the 2*KH dim
  hands each shard its own `[K_local, V_local]` halves.

This module is the XLA path: exact, gather-based, used on CPU CI and as the
fallback; the serving hot path on TPU is ops/paged_attention_pallas.py.

Shapes:
  q:            (B, S, H, D)
  kv cache:     (L, N, bs, 2*KH, D) fused, or a single layer (N, bs, 2*KH, D)
  block_tables: (B, M) int32 — padded with 0s beyond the sequence's blocks
  context_lens: (B,)  int32 — total tokens in cache per sequence (incl. chunk)
  q_positions:  (B, S) int32 — absolute position per query token, -1 for pad
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def combine_kv(k: jnp.ndarray, v: jnp.ndarray, tp: int = 1) -> jnp.ndarray:
    """(T, KH, D) k and v → (T, 2*KH, D) shard-grouped update slab."""
    T, KH, D = k.shape
    hp = KH // tp
    stacked = jnp.stack(
        [k.reshape(T, tp, hp, D), v.reshape(T, tp, hp, D)], axis=2
    )  # (T, tp, 2, hp, D)
    return stacked.reshape(T, 2 * KH, D)


def split_kv(kv: jnp.ndarray, tp: int = 1) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Inverse of combine_kv on any (..., 2*KH, D) array."""
    *lead, KH2, D = kv.shape
    KH = KH2 // 2
    hp = KH // tp
    r = kv.reshape(*lead, tp, 2, hp, D)
    k = r[..., :, 0, :, :].reshape(*lead, KH, D)
    v = r[..., :, 1, :, :].reshape(*lead, KH, D)
    return k, v


def write_kv(
    cache: jnp.ndarray,
    layer_idx: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    slot_mapping: jnp.ndarray,
    tp: int = 1,
) -> jnp.ndarray:
    """Scatter T tokens' K+V into layer ``layer_idx`` of the fused cache with
    ONE scatter (in place through a donated scan carry). k/v: (T, KH, D);
    slot_mapping: (T,) flat block*block_size+offset, -1 = dropped padding."""
    L, n, bs, KH2, D = cache.shape
    slots = jnp.where(slot_mapping < 0, n * bs, slot_mapping)
    update = combine_kv(k.astype(cache.dtype), v.astype(cache.dtype), tp)
    flat = cache.reshape(L, n * bs, KH2, D)
    flat = flat.at[layer_idx, slots].set(update, mode="drop", unique_indices=True)
    return flat.reshape(L, n, bs, KH2, D)


def paged_attention(
    q: jnp.ndarray,
    kv_layer: jnp.ndarray,  # (N, bs, 2*KH, D) — one layer of the pool
    block_tables: jnp.ndarray,
    context_lens: jnp.ndarray,
    q_positions: jnp.ndarray,
    tp: int = 1,
    scale: float | None = None,
    soft_cap: float = 0.0,
    window: int = 0,
) -> jnp.ndarray:
    B, S, H, D = q.shape
    n, block_size, KH2, _ = kv_layer.shape
    KH = KH2 // 2
    M = block_tables.shape[1]
    G = H // KH
    scale = scale if scale is not None else D**-0.5

    # Gather context: (B, M, bs, 2KH, D) -> (B, Tc, KH, D) k and v
    gathered = kv_layer[block_tables].reshape(B, M * block_size, KH2, D)
    k, v = split_kv(gathered, tp)

    kv_pos = jnp.arange(M * block_size, dtype=jnp.int32)[None, :]  # (1, Tc)
    valid_kv = kv_pos < context_lens[:, None]  # (B, Tc)
    causal = kv_pos[:, None, :] <= q_positions[:, :, None]  # (B, S, Tc)
    if window:  # row t sees rows t - window + 1 .. t
        causal &= kv_pos[:, None, :] > q_positions[:, :, None] - window
    valid_q = q_positions >= 0  # (B, S)
    mask = valid_kv[:, None, :] & causal & valid_q[:, :, None]

    qg = q.reshape(B, S, KH, G, D)
    scores = jnp.einsum(
        "bskgd,btkd->bkgst", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if soft_cap:  # Gemma-2 score capping, before masking (HF order)
        scores = soft_cap * jnp.tanh(scores / soft_cap)
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    denom = probs.sum(axis=-1, keepdims=True)
    probs = probs / jnp.maximum(denom, 1e-30)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(B, S, H, D).astype(q.dtype)


def ragged_paged_attention(
    q: jnp.ndarray,  # (T, H, D) packed query stream
    kv_layer: jnp.ndarray,  # (N, bs, 2*KH, D) — one layer of the pool
    block_tables: jnp.ndarray,  # (S, M) per-slot block rows
    context_lens: jnp.ndarray,  # (S,) total context per slot
    seq_ids: jnp.ndarray,  # (T,) owning slot per token (any value when padded)
    q_positions: jnp.ndarray,  # (T,) absolute position per token, -1 = pad
    tp: int = 1,
    scale: float | None = None,
    soft_cap: float = 0.0,
    window: int = 0,
) -> jnp.ndarray:
    """XLA reference for the ragged kernel: the packed mixed
    prefill+decode stream — including speculative verify spans, which are
    just short prefill-shaped spans of ``1 + k`` tokens ending at the
    slot's context — attended per token against its owning slot's paged
    context (ops/ragged_paged_attention_pallas.py is the TPU hot path;
    this is the CPU/fallback path and the parity oracle).

    Padding tokens (q_positions < 0) produce finite garbage, exactly like
    ``paged_attention``'s inactive rows — their logits are discarded
    downstream."""
    T, H, D = q.shape
    n, block_size, KH2, _ = kv_layer.shape
    KH = KH2 // 2
    M = block_tables.shape[1]
    G = H // KH
    scale = scale if scale is not None else D**-0.5

    sid = jnp.clip(seq_ids, 0, block_tables.shape[0] - 1)
    # per-token context gather: (T, M, bs, 2KH, D) -> (T, Tc, KH, D)
    gathered = kv_layer[block_tables[sid]].reshape(
        T, M * block_size, KH2, D
    )
    k, v = split_kv(gathered, tp)

    kv_pos = jnp.arange(M * block_size, dtype=jnp.int32)[None, :]  # (1, Tc)
    valid_kv = kv_pos < context_lens[sid][:, None]  # (T, Tc)
    causal = kv_pos <= q_positions[:, None]  # (T, Tc)
    if window:  # row t sees rows t - window + 1 .. t; blocks wholly below
        # may have been given back (engine/scheduler.py): masked, not read
        causal &= kv_pos > q_positions[:, None] - window
    mask = valid_kv & causal & (q_positions >= 0)[:, None]

    qg = q.reshape(T, KH, G, D)
    scores = jnp.einsum(
        "tkgd,tckd->tkgc", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if soft_cap:  # Gemma-2 score capping, before masking (HF order)
        scores = soft_cap * jnp.tanh(scores / soft_cap)
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    denom = probs.sum(axis=-1, keepdims=True)
    probs = probs / jnp.maximum(denom, 1e-30)
    out = jnp.einsum("tkgc,tckd->tkgd", probs, v.astype(jnp.float32))
    return out.reshape(T, H, D).astype(q.dtype)


# -- latent attention (MLA), absorbed ----------------------------------------
# The pool holds one row a token, (L, N, bs, lanes): the latent c (its first
# ``value_dim`` lanes), then the rotated key every head shares, then zeros
# up to whole 128-lane tiles. A query head scores a row over all its lanes
# and takes the row's first ``value_dim`` lanes as the value: KH = 1, keys
# and values the same bytes.

def write_latent(
    cache: jnp.ndarray,  # (L, N, bs, lanes)
    layer_idx: jnp.ndarray,
    rows: jnp.ndarray,  # (T, lanes)
    slot_mapping: jnp.ndarray,  # (T,) flat slots, -1 = dropped padding
) -> jnp.ndarray:
    """Scatter T tokens' latent rows into layer ``layer_idx`` with ONE
    scatter, in place through a donated scan carry (the TPU compiler keeps
    the pool where it lies when ``lanes`` is whole tiles)."""
    L, n, bs, lanes = cache.shape
    slots = jnp.where(slot_mapping < 0, n * bs, slot_mapping)
    flat = cache.reshape(L, n * bs, lanes)
    flat = flat.at[layer_idx, slots].set(rows.astype(cache.dtype),
                                         mode="drop", unique_indices=True)
    return flat.reshape(L, n, bs, lanes)


def latent_ragged_paged_attention(
    q: jnp.ndarray,  # (T, H, lanes) packed stream, absorbed queries
    rows_layer: jnp.ndarray,  # (N, bs, lanes) — one layer of the pool
    block_tables: jnp.ndarray,  # (S, M)
    context_lens: jnp.ndarray,  # (S,)
    seq_ids: jnp.ndarray,  # (T,) owning slot per token
    q_positions: jnp.ndarray,  # (T,) absolute position, -1 = pad
    value_dim: int,
) -> jnp.ndarray:
    """XLA form of ops/latent_paged_attention_pallas.py (the CPU path and
    the parity oracle): every token of the packed stream against its
    slot's paged latent rows, causally, scores scaled by lanes ** -0.5 as
    the kernel's (the mixer folds the model's own scale into q). Returns
    (T, H, value_dim)."""
    T, H, lanes = q.shape
    n, block_size, _ = rows_layer.shape
    M = block_tables.shape[1]
    scale = lanes ** -0.5

    sid = jnp.clip(seq_ids, 0, block_tables.shape[0] - 1)
    ctx = rows_layer[block_tables[sid]].reshape(
        T, M * block_size, lanes).astype(jnp.float32)
    kv_pos = jnp.arange(M * block_size, dtype=jnp.int32)[None, :]
    mask = ((kv_pos < context_lens[sid][:, None])
            & (kv_pos <= q_positions[:, None])
            & (q_positions >= 0)[:, None])  # (T, Tc)
    scores = jnp.einsum("thw,tcw->thc", q.astype(jnp.float32), ctx) * scale
    scores = jnp.where(mask[:, None], scores, NEG_INF)
    probs = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    probs = probs / jnp.maximum(probs.sum(axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("thc,tcv->thv", probs, ctx[..., :value_dim])
    return out.astype(q.dtype)
