"""Ragged paged attention — ONE Pallas kernel for mixed prefill+decode.

The bucketed kernels (ops/paged_attention_pallas.py) split every engine
step into a decode dispatch over padded slot grids and a prefill dispatch
compiled once per power-of-two token bucket. This kernel consumes the
packed token stream directly ("Ragged Paged Attention", PAPERS.md):

- queries arrive as one ``(T, H, D)`` stream — the concatenation of every
  scheduled sequence's span (a prefill chunk of any length, a decode row
  of one token, a speculative verify span of ``1 + k`` tokens — the last
  accepted token followed by ``k`` n-gram drafts, attended causally so
  position ``j`` scores every draft against the model's own prediction in
  one pass — or an empty span for an inactive slot), described by
  ``cu_q_lens (S+1,)`` cumulative span offsets;
- the grid is tiled over fixed ``q_tile`` windows of the stream, NOT over
  sequences: a tile that straddles sequence boundaries walks each
  overlapping sequence in turn (per-tile first/count metadata is computed
  by the wrapper with one ``searchsorted`` over ``cu_q_lens``), carrying
  ONE flash-softmax state across the walk — rows outside the current
  sequence contribute exactly-zero probability mass;
- per sequence, the paged context is streamed exactly like the bucketed
  kernels: windowed double-buffered block DMAs with per-BLOCK predication
  on the tile's causal reach (the roofline's over-read fix), causal
  masking within the ragged span, NaN-safe V zeroing past the reach.

There are no padding lanes between spans and no shape buckets: the only
compile-relevant shape is the budget-padded ``T`` (tokens the scheduler
may batch) and the fixed ``S`` slot count, so the steady-state engine
compiles this program exactly once — speculative verification included,
since a verify span is just a short prefill-shaped span and the kernel
never distinguishes the two. Tail padding past ``cu_q_lens[-1]``
belongs to no sequence and computes to zeros.

The matching ragged KV write is ``kv_cache_write_pallas`` (paged_
attention_pallas.py), which already takes a flat per-token slot mapping
with -1 skips — the packed stream is its native input.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ragged_kernel(
    # scalar prefetch
    bt_ref,  # (S, M) SMEM — per-slot block-table rows
    cu_ref,  # (S+1,) SMEM — cumulative query-span offsets into the stream
    cl_ref,  # (S,) SMEM — total context per slot (incl. this step's span)
    tfirst_ref,  # (nt,) SMEM — first sequence overlapping each tile
    tcnt_ref,  # (nt,) SMEM — sequences overlapping each tile
    layer_ref,  # (1,) SMEM
    # inputs
    q_ref,  # (1, R, KH, D) VMEM — R = q_tile*G rows of this tile
    kv_hbm,  # (L, N, bs, 2KH, D) ANY
    # outputs
    o_ref,  # (1, R, KH, D) VMEM
    # scratch
    buf,  # (2, W, bs, 2KH, D) VMEM
    sems,  # (2, W) DMA sems
    *,
    block_size: int,
    windows: int,
    q_tile: int,
    group: int,
    scale: float,
    soft_cap: float = 0.0,
):
    t = pl.program_id(0)
    layer = layer_ref[0]
    W = windows
    bs = block_size
    win_tokens = W * bs
    _, R, KH, D = q_ref.shape
    TQ = q_tile
    first = tfirst_ref[t]
    cnt = tcnt_ref[t]

    q = q_ref[0].astype(jnp.float32)  # (R, KH, D)
    # row r is stream token g = t*TQ + r//G (rows ordered (token, g))
    g_idx = t * TQ + jax.lax.broadcasted_iota(
        jnp.int32, (1, R, 1), 1
    ) // group  # (1, R, 1)

    def seq_body(si, carry):
        """Walk one sequence's paged context for the rows it owns in this
        tile. The flash carry persists ACROSS sequences: each row belongs
        to exactly one span, and rows outside the current span get
        explicit zero probability (see the masked-p note below), so
        foreign sequences never move a row's (m, l, acc)."""
        s = first + si
        q_start = cu_ref[s]
        q_end = cu_ref[s + 1]
        ctx = cl_ref[s]
        q_len = q_end - q_start
        row_in = (g_idx >= q_start) & (g_idx < q_end)  # (1, R, 1)
        # absolute position of each owned query token; garbage elsewhere
        # (masked by row_in)
        qpos = ctx - q_len + (g_idx - q_start)
        # causal reach of this sequence's LAST token in this tile — the
        # per-block DMA predicate, so the tail over-read stays one block
        last_g = jnp.minimum(q_end, (t + 1) * TQ) - 1
        reach = jnp.minimum(ctx, ctx - q_len + (last_g - q_start) + 1)
        # empty spans (inactive slots, seqs not in this step) skip the
        # whole context walk
        reach = jnp.where(q_len > 0, reach, 0)
        nwin = pl.cdiv(reach, win_tokens)

        def dma(slot, w, j):
            bid = bt_ref[s, w * W + j]
            return pltpu.make_async_copy(
                kv_hbm.at[layer, bid], buf.at[slot, j], sems.at[slot, j]
            )

        def block_active(w, j):
            return w * win_tokens + j * bs < reach

        def issue(slot, w):
            for j in range(W):
                @pl.when(block_active(w, j))
                def _():
                    dma(slot, w, j).start()

        @pl.when(nwin > 0)
        def _():
            issue(0, 0)

        def win_body(w, carry2):
            m, l, acc = carry2
            slot = jax.lax.rem(w, 2)

            @pl.when(w + 1 < nwin)
            def _():
                issue(jax.lax.rem(w + 1, 2), w + 1)

            for j in range(W):
                @pl.when(block_active(w, j))
                def _():
                    dma(slot, w, j).wait()

            kv = buf[slot].reshape(win_tokens, 2 * KH, D)
            s_heads = []
            for h in range(KH):
                k_h = kv[:, h, :].astype(jnp.float32)  # (T, D)
                s_heads.append(
                    jax.lax.dot_general(
                        q[:, h, :], k_h, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                )  # (R, T)
            sc = jnp.stack(s_heads) * scale  # (KH, R, T)
            if soft_cap:  # Gemma-2 score capping, before masking
                sc = soft_cap * jnp.tanh(sc / soft_cap)
            kvpos = w * win_tokens + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, win_tokens), 2
            )
            valid = row_in & (kvpos <= qpos) & (kvpos < ctx)  # (1, R, T)
            sc = jnp.where(valid, sc, NEG_INF)

            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            # masked-p: a row NOT owned by this sequence has every score
            # at NEG_INF. If that row is still untouched (m == NEG_INF),
            # exp(sc - m_new) = exp(0) = 1 would inflate its l by T per
            # window — so invalid lanes are zeroed EXPLICITLY rather than
            # through the exp underflow the bucketed kernels rely on.
            p = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            # blocks past `reach` were never DMA'd: zero their V rows —
            # 0 x NaN = NaN would poison the accumulator through
            # masked-out weights
            vvalid = (w * win_tokens + jax.lax.broadcasted_iota(
                jnp.int32, (win_tokens, 1), 0) < reach)
            acc_heads = []
            for h in range(KH):
                v_h = jnp.where(
                    vvalid, kv[:, KH + h, :].astype(jnp.float32), 0.0
                )
                acc_heads.append(
                    jax.lax.dot_general(
                        p[h], v_h, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                )  # (R, D)
            acc_new = acc * alpha + jnp.stack(acc_heads)
            return m_new, l_new, acc_new

        return jax.lax.fori_loop(0, nwin, win_body, carry)

    init = (
        jnp.full((KH, R, 1), NEG_INF, jnp.float32),
        jnp.zeros((KH, R, 1), jnp.float32),
        jnp.zeros((KH, R, D), jnp.float32),
    )
    m, l, acc = jax.lax.fori_loop(0, cnt, seq_body, init)
    # rows owned by no sequence (tail padding) kept l = 0 → output 0
    out = acc / jnp.maximum(l, 1e-30)  # (KH, R, D)
    o_ref[0] = out.transpose(1, 0, 2).astype(o_ref.dtype)


def tile_metadata(
    cu_q_lens: jnp.ndarray,  # (S+1,) int32
    num_tiles: int,
    q_tile: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-tile (first overlapping sequence, overlap count) from the span
    offsets — jit-safe (one searchsorted, static shapes). Tiles past the
    packed total get count 0; empty spans strictly inside an overlap range
    are included but walk zero windows in the kernel."""
    cu = jnp.asarray(cu_q_lens, jnp.int32)
    S = cu.shape[0] - 1
    total = cu[S]
    starts = jnp.arange(num_tiles, dtype=jnp.int32) * q_tile
    g_last = jnp.minimum(starts + q_tile, total) - 1
    first = jnp.clip(
        jnp.searchsorted(cu, starts, side="right").astype(jnp.int32) - 1,
        0, S - 1,
    )
    last = jnp.clip(
        jnp.searchsorted(cu, g_last, side="right").astype(jnp.int32) - 1,
        0, S - 1,
    )
    cnt = jnp.where(g_last >= starts, last - first + 1, 0)
    return first, cnt


def ragged_paged_attention_pallas(
    q: jnp.ndarray,  # (T, H, D) packed query stream
    kv_cache: jnp.ndarray,  # (L, N, bs, 2KH, D)
    block_tables: jnp.ndarray,  # (S, M) per-slot block rows
    cu_q_lens: jnp.ndarray,  # (S+1,) int32 cumulative span offsets
    context_lens: jnp.ndarray,  # (S,) int32 total context per slot
    layer_idx: jnp.ndarray | int = 0,
    q_tile: int = 128,
    windows: int = 8,
    interpret: bool = False,
    soft_cap: float = 0.0,
) -> jnp.ndarray:
    T, H, D = q.shape
    L, N, bs, KH2, _ = kv_cache.shape
    KH = KH2 // 2
    G = H // KH
    TQ = min(q_tile, T)
    Tp = -(-T // TQ) * TQ
    if Tp != T:  # tail-pad the stream to a tile multiple (rows → zeros)
        q = jnp.pad(q, ((0, Tp - T), (0, 0), (0, 0)))
    nt = Tp // TQ
    R = TQ * G

    tfirst, tcnt = tile_metadata(cu_q_lens, nt, TQ)
    # rows ordered (token, g): (Tp, H, D) -> (nt, TQ*G, KH, D)
    q_rows = (
        q.reshape(Tp, KH, G, D).transpose(0, 2, 1, 3).reshape(nt, R, KH, D)
    )
    layer_arr = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((1, R, KH, D), lambda t, *_: (t, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, R, KH, D), lambda t, *_: (t, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, windows, bs, KH2, D), kv_cache.dtype),
            pltpu.SemaphoreType.DMA((2, windows)),
        ],
    )
    kernel = functools.partial(
        _ragged_kernel, block_size=bs, windows=windows, q_tile=TQ,
        group=G, scale=D**-0.5, soft_cap=soft_cap,
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((nt, R, KH, D), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="ragged_paged_attention",
    )(
        jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(cu_q_lens, jnp.int32),
        jnp.asarray(context_lens, jnp.int32),
        tfirst,
        tcnt,
        layer_arr,
        q_rows,
        kv_cache,
    )
    # rows (token, g) back to (T, H, D) with h = kh*G + g
    return (
        out.reshape(Tp, G, KH, D).transpose(0, 2, 1, 3).reshape(Tp, H, D)[:T]
    )
