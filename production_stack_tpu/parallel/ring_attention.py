"""Ring attention: sequence/context parallelism over the ``seq`` mesh axis.

The missing long-context piece the reference delegates nowhere (SURVEY.md
§5.7 — no ring attention, Ulysses, or context parallelism exists in that
stack): sequences longer than one device's memory are sharded along the
sequence dim; K/V shards rotate around the ring via ``lax.ppermute`` while
every device keeps a flash-style running softmax for its local queries.
Communication rides the ICI ring, overlapping with each step's matmul —
the XLA-collective formulation of the blockwise-ring pattern (Liu et al.),
not a hand-scheduled NCCL pipeline.

Usage: wrap in shard_map with q/k/v sharded along the sequence dimension on
``axis_name`` (see ``ring_causal_attention`` for the jit-level wrapper).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


NEG_INF = -1e30


def _ring_attention_local(
    q: jnp.ndarray,  # (B, Sl, H, D) local query shard
    k: jnp.ndarray,  # (B, Sl, KH, D) local key shard
    v: jnp.ndarray,
    axis_name: str,
    causal: bool = True,
    soft_cap: float = 0.0,
) -> jnp.ndarray:
    """Per-shard body (runs under shard_map)."""
    B, Sl, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    scale = D**-0.5

    qg = q.reshape(B, Sl, KH, G, D).astype(jnp.float32)
    q_pos = my * Sl + jnp.arange(Sl, dtype=jnp.int32)  # global positions

    def step(carry, i):
        k_cur, v_cur, m, l, acc = carry
        src = (my - i) % n  # whose shard we currently hold
        kv_pos = src * Sl + jnp.arange(Sl, dtype=jnp.int32)

        s = jnp.einsum(
            "bqkgd,bskd->bkgqs", qg, k_cur.astype(jnp.float32)
        ) * scale  # (B, KH, G, Sl, Sl)
        if soft_cap:  # Gemma-2 score capping, before masking
            s = soft_cap * jnp.tanh(s / soft_cap)
        if causal:
            mask = kv_pos[None, :] <= q_pos[:, None]  # (Sl, Sl)
            s = jnp.where(mask[None, None, None], s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bkgqs,bskd->bkgqd", p, v_cur.astype(jnp.float32)
        )
        # rotate K/V to the next device; overlap with the next step's matmul
        k_nxt = lax.ppermute(k_cur, axis_name, [(j, (j + 1) % n) for j in range(n)])
        v_nxt = lax.ppermute(v_cur, axis_name, [(j, (j + 1) % n) for j in range(n)])
        return (k_nxt, v_nxt, m_new, l_new, acc_new), None

    init = (
        k, v,
        jnp.full((B, KH, G, Sl, 1), NEG_INF, jnp.float32),
        jnp.zeros((B, KH, G, Sl, 1), jnp.float32),
        jnp.zeros((B, KH, G, Sl, D), jnp.float32),
    )
    (k, v, m, l, acc), _ = lax.scan(step, init, jnp.arange(n))
    out = acc / jnp.maximum(l, 1e-30)  # (B, KH, G, Sl, D)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sl, H, D).astype(q.dtype)


def ring_causal_attention(
    q: jnp.ndarray,  # (B, S, H, D) global
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "seq",
    head_axis: str | None = None,
    soft_cap: float = 0.0,
) -> jnp.ndarray:
    """jit-level wrapper: shards the sequence dim over ``axis_name`` and runs
    the ring. S must divide the axis size. ``head_axis`` additionally shards
    the head dim (tensor parallelism composes: heads are independent, so the
    ring only ever talks over ``axis_name``)."""
    spec = P(None, axis_name, head_axis, None)
    # stackcheck: disable=jit-cache-hygiene — ring_causal_attention runs
    # at trace time inside a jitted model forward; the shard_map is part
    # of the enclosing trace and is never rebuilt per dispatch
    fn = jax.shard_map(
        functools.partial(_ring_attention_local, axis_name=axis_name,
                          soft_cap=soft_cap),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
