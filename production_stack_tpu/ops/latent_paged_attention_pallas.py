"""Latent paged attention (MLA, absorbed) — ONE Pallas kernel for the ragged
stream and for decode rows alike.

The pool holds one row a token and cache layer, ``(L, N, bs, lanes)``: the
latent ``c`` (the first ``value_dim`` lanes), the rotated key every head
shares, zeros up to whole 128-lane tiles. All ``H`` query heads of a token
score the SAME row over all its lanes and take its first ``value_dim``
lanes as the value (KH = 1, G = H, keys and values the same bytes), so a
window of the context is two matmuls for a whole tile: ``(R, lanes) x
(lanes, T)`` and ``(R, T) x (T, value_dim)`` with ``R = q_tile * H`` rows.
No per-head loop and no slab slicing: at H = 128 one token already fills
the MXU's rows, which is why a decode row needs no kernel of its own.

The interface, the tiling of the stream, the walk of each overlapping span
with one flash-softmax state a tile, the windowed double-buffered block
DMAs, their per-block predication on the causal reach and the masked
probabilities are those of ops/ragged_paged_attention_pallas.py (whose
``tile_metadata`` and ``interior_windows`` this kernel calls). What
differs in the walk: a span that owns ONE token of a tile (a decode row)
computes on that token's ``H`` rows alone, any other span on the whole
tile; windows that nothing can mask (the interior of a full tile's
context) skip the mask.

Operands go to the MXU as stored (bf16 at the published widths), the
scores, the softmax state and the accumulator are float32, the weights are
rounded to the cache's type before the second matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops.ragged_paged_attention_pallas import (
    LANES,
    NEG_INF,
    WINDOWS,
    interior_windows,
    tile_metadata,
)

# stream tokens a grid step owns: 16 x 128 heads = 2048 rows a tile, whose
# query and output blocks, float32 accumulator and window temporaries ask
# for 18.98 MiB of scoped VMEM at the published widths (the compiler's
# count, tests/test_kernel_names_v5e.py), over the default 16 MiB: the
# call sets its own limit, so the configuration needs no libtpu flag
Q_TILE = 16
VMEM_LIMIT_BYTES = 64 * 2 ** 20


def _latent_kernel(
    # scalar prefetch
    bt_ref,  # (S, M) SMEM — per-slot block-table rows
    cu_ref,  # (S+1,) SMEM — cumulative query-span offsets into the stream
    cl_ref,  # (S,) SMEM — total context per slot (incl. this step's span)
    tfirst_ref,  # (nt,) SMEM — first sequence overlapping each tile
    tcnt_ref,  # (nt,) SMEM — sequences overlapping each tile
    layer_ref,  # (1,) SMEM
    # inputs
    q_ref,  # (1, R, lanes) VMEM — R = q_tile * H rows, ordered (token, head)
    kv_hbm,  # (L, N, bs, lanes) ANY
    # outputs
    o_ref,  # (1, R, value_dim) VMEM
    # scratch
    buf,  # (2, W, bs, lanes) VMEM
    sems,  # (2, W) DMA sems
    m_ref,  # (R, LANES) f32 — flash running max, lane 0
    l_ref,  # (R, LANES) f32 — flash running sum, lane 0
    acc_ref,  # (R, value_dim) f32 — flash accumulator
    *,
    block_size: int,
    windows: int,
    q_tile: int,
    heads: int,
    scale: float,
):
    t = pl.program_id(0)
    layer = layer_ref[0]
    W, bs, TQ, H = windows, block_size, q_tile, heads
    win_tokens = W * bs
    R, lanes = q_ref.shape[1:]
    V = o_ref.shape[-1]
    first = tfirst_ref[t]
    cnt = tcnt_ref[t]
    tile0 = t * TQ

    # one flash state a tile, across its spans: a row belongs to one span,
    # and rows outside the walking span get exactly zero probability
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def seq_body(si, _):
        s = first + si
        q_start = cu_ref[s]
        q_end = cu_ref[s + 1]
        ctx = cl_ref[s]
        q_len = q_end - q_start
        lo = jnp.maximum(q_start, tile0) - tile0
        hi = jnp.minimum(q_end, tile0 + TQ) - tile0
        # causal reach of the span's last token in this tile: the DMA
        # predicate; an empty span walks nothing
        reach = jnp.minimum(ctx, ctx - q_len + (tile0 + hi - 1 - q_start) + 1)
        reach = jnp.where(q_len > 0, reach, 0)
        nwin = pl.cdiv(reach, win_tokens)

        def dma(slot, w, j):
            return pltpu.make_async_copy(
                kv_hbm.at[layer, bt_ref[s, w * W + j]], buf.at[slot, j],
                sems.at[slot, j])

        def block_active(w, j):
            return w * win_tokens + j * bs < reach

        def issue(slot, w):
            for j in range(W):
                @pl.when(block_active(w, j))
                def _():
                    dma(slot, w, j).start()

        def walk(rows, r0):
            """Stream the context past tile rows [r0, r0 + rows): the
            whole tile, or the one token a decode row owns."""
            rs = pl.ds(r0, rows)
            # row r is stream token tile0 + r // H (rows ordered (token, h))
            g_idx = tile0 + (r0 + jax.lax.broadcasted_iota(
                jnp.int32, (rows, 1), 0)) // H
            row_in = (g_idx >= q_start) & (g_idx < q_end)
            qpos = ctx - q_len + (g_idx - q_start)  # garbage where not row_in

            issue(0, 0)

            def win_body(masked, w, _):
                slot = jax.lax.rem(w, 2)

                @pl.when(w + 1 < nwin)
                def _():
                    issue(jax.lax.rem(w + 1, 2), w + 1)

                for j in range(W):
                    @pl.when(block_active(w, j))
                    def _():
                        dma(slot, w, j).wait()

                k = buf[slot].reshape(win_tokens, lanes)
                sc = jax.lax.dot_general(
                    q_ref[0, rs, :], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale  # (rows, T)
                m = m_ref[rs, 0:1]
                if masked:
                    kvpos = w * win_tokens + jax.lax.broadcasted_iota(
                        jnp.int32, (1, win_tokens), 1)
                    valid = row_in & (kvpos <= qpos) & (kvpos < ctx)
                    sc = jnp.where(valid, sc, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(sc - m_new)
                v = k[:, :V]
                if masked:
                    # a row this span does not own has every score at
                    # NEG_INF: exp(sc - m_new) would be 1 there, so the
                    # masked weights are zeroed explicitly; blocks past the
                    # reach were never fetched, and 0 x NaN = NaN
                    p = jnp.where(valid, p, 0.0)
                    v = jnp.where(
                        w * win_tokens + jax.lax.broadcasted_iota(
                            jnp.int32, (win_tokens, 1), 0) < reach,
                        v, jnp.zeros_like(v))
                l_ref[rs, 0:1] = l_ref[rs, 0:1] * alpha + jnp.sum(
                    p, axis=-1, keepdims=True)
                m_ref[rs, 0:1] = m_new
                acc_ref[rs, :] = acc_ref[rs, :] * alpha + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return 0

            n_int = 0
            if rows == R:
                # windows every row of a full tile reaches whole
                n_int = jnp.minimum(nwin, interior_windows(
                    lo, hi, TQ, ctx - q_len + (tile0 - q_start), win_tokens))
                jax.lax.fori_loop(
                    0, n_int, functools.partial(win_body, False), 0)
            jax.lax.fori_loop(
                n_int, nwin, functools.partial(win_body, True), 0)

        live = nwin > 0
        if TQ == 1:
            pl.when(live)(lambda: walk(R, 0))
        else:
            one = hi - lo == 1
            pl.when(live & one)(
                lambda: walk(H, pl.multiple_of(lo * H, H)))
            pl.when(live & jnp.logical_not(one))(lambda: walk(R, 0))
        return 0

    jax.lax.fori_loop(0, cnt, seq_body, 0)
    # rows no span owns (tail padding, idle slots) kept l = 0: output 0
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, 0:1], 1e-30)
                ).astype(o_ref.dtype)


def latent_paged_attention_pallas(
    q: jnp.ndarray,  # (T, H, lanes) packed stream of absorbed queries
    kv_cache: jnp.ndarray,  # (L, N, bs, lanes)
    block_tables: jnp.ndarray,  # (S, M) per-slot block rows
    cu_q_lens: jnp.ndarray,  # (S+1,) int32 cumulative span offsets
    context_lens: jnp.ndarray,  # (S,) int32 total context per slot
    layer_idx: jnp.ndarray | int = 0,
    *,
    value_dim: int,
    q_tile: int = Q_TILE,
    windows: int = WINDOWS,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns (T, H, value_dim). A decode step calls it with one-token
    spans (``cu_q_lens = arange(S + 1)``; an idle slot's context 0 walks
    nothing and reads zeros)."""
    T, H, lanes = q.shape
    L, N, bs, _ = kv_cache.shape
    TQ = min(q_tile, T)
    Tp = -(-T // TQ) * TQ
    if Tp != T:  # tail-pad the stream to whole tiles (rows -> zeros)
        q = jnp.pad(q, ((0, Tp - T), (0, 0), (0, 0)))
    nt = Tp // TQ
    R = TQ * H

    tfirst, tcnt = tile_metadata(cu_q_lens, nt, TQ)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((1, R, lanes), lambda t, *_: (t, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, R, value_dim), lambda t, *_: (t, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, windows, bs, lanes), kv_cache.dtype),
            pltpu.SemaphoreType.DMA((2, windows)),
            pltpu.VMEM((R, LANES), jnp.float32),
            pltpu.VMEM((R, LANES), jnp.float32),
            pltpu.VMEM((R, value_dim), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _latent_kernel, block_size=bs, windows=windows, q_tile=TQ, heads=H,
        scale=lanes ** -0.5)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((nt, R, value_dim), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="latent_paged_attention",
    )(
        jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(cu_q_lens, jnp.int32),
        jnp.asarray(context_lens, jnp.int32),
        tfirst,
        tcnt,
        jnp.asarray(layer_idx, jnp.int32).reshape(1),
        q.reshape(nt, R, lanes),
        kv_cache,
    )
    return out.reshape(Tp, H, value_dim)[:T]
