"""Ragged paged attention — ONE Pallas kernel for mixed prefill+decode.

The engine packs every prompt chunk and decode row of a step into one
token stream, and this kernel consumes that stream as it lies: no padded
slot grid, no chunk padded up to a shape class ("Ragged Paged Attention",
PAPERS.md):

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
- per sequence, the paged context is streamed as the decode kernel streams
  it: windowed double-buffered block DMAs with per-BLOCK predication
  on the tile's causal reach (the roofline's over-read fix), causal
  masking within the ragged span, NaN-safe V zeroing past the reach.

A walk computes on the rows its span owns, not on the whole tile: where
those rows fit ``ROW_BLOCK`` (a decode row, a verify span, the short head
or tail of a chunk that straddles a tile) the QK, softmax update and PV
run on the aligned ``ROW_BLOCK``-row block that covers them, and on all
``q_tile * G`` rows otherwise (``narrow_walk`` decides, from the span
offsets alone). The flash state lives in VMEM scratch, head-major, so a
walk reads and writes just its block; rows of the block that belong to a
neighbouring span stay masked as everywhere else.

A full-tile walk streams the *interior* of its context (the windows that
end at or below the position of the tile's first token, counted by
``interior_windows`` from the scalars the kernel already holds) through a
window body of its own: nothing in such a window can be masked, so it
builds no mask, takes each head's K and V from the landed slab in bf16
with one sublane-strided load a head pair, and keeps the flash state at
register width. The one or two windows left (the causal diagonal, the
context's tail) take the masked body, as every walk of a narrow block or
of a tile shared between spans does.

A layer with a sliding ``window`` (row t sees rows t - window + 1 .. t;
0 = none, and the traced program is then what it was) gives every walk a
floor beside its causal reach: the walk starts at the context window that
holds the floor of its first row, blocks wholly below the floor are neither
fetched nor scored (the engine may have given them back: engine/
scheduler.py), and keys below a row's own floor are masked. A windowed
walk takes the masked body throughout (the interior body assumes that
nothing can be masked and that it runs first).

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
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops.paged_attention_pallas import slab_heads

NEG_INF = -1e30
WINDOWS = 8  # KV blocks a context window holds: 128 tokens at block 16
# lanes of the flash state's scratch: a vector register's width. The
# masked body keeps a row's (m, l) in lane 0, as a ``(KH, R, 1)`` array
# would lie there; the interior body uses all of them
LANES = 128
Q_TILE = 128  # stream tokens a grid step owns, at up to 4 query heads a KV head


# KV heads up to which a tile keeps its tokens (OLMoE's and Ouro's 16)
TILE_KV_HEADS = 16


def q_tile_for(group: int, kv_heads: int = TILE_KV_HEADS) -> int:
    """Stream tokens a grid step owns at ``group`` query heads a KV head
    and ``kv_heads`` KV heads.
    A tile's rows are tokens x group, and its query block, output block
    and float32 accumulators grow with them: 128 tokens at G = 8 ask for
    40 MiB of scoped VMEM (the compiler's count for KH = 8, D = 128), over
    what the cells' 32 MiB flag allows. Past G = 4 the tile shrinks so that
    it keeps at most G = 4's 512 rows; every geometry up to G = 4 keeps
    128. Where the group does not divide 512 the tile shrinks further, to
    rows that are whole ``ROW_ALIGN`` tiles (G = 5: 96 tokens, 480 rows; a
    power of two keeps what it had): a tile of 510 rows has no narrow
    block at all (``narrow_walk``), and every decode row of a ragged step
    would take the whole tile's body.

    The same blocks grow with the KV heads too, and the landed windows of
    the cache besides: KH = 16, G = 1 asks 10.60 MiB of the default 16, and
    32 heads at the same 128 tokens 21.18 (the compiler's counts;
    tests/test_kernel_names_v5e.py). Past ``TILE_KV_HEADS`` the tile
    shrinks so that tokens x KV heads stay what 16 heads have (32 heads: 64
    tokens); every geometry up to 16 KV heads keeps its tile."""
    tokens = Q_TILE if group <= 4 else Q_TILE * 4 // group
    if kv_heads > TILE_KV_HEADS:
        tokens = tokens * TILE_KV_HEADS // kv_heads
    elif group <= 4:
        return Q_TILE
    return max(tokens - tokens % (ROW_ALIGN // math.gcd(group, ROW_ALIGN)),
               16)


# Rows a narrow walk computes on: a one-token span at any G <= 16 and a
# 1 + 4 verify span at G = 4 (20 rows from a multiple of 4) fit it at any
# offset. The MXU streams these rows past each (128 x 128) key tile, so
# 32 costs little more than 8 (chosen on the chip, PERF.md section 6).
ROW_BLOCK = 32
# a narrow block starts at a multiple of the bf16 sublane tile, so the
# query rows load without a relayout
ROW_ALIGN = 16


def narrow_walk(lo, hi, group: int, rows: int, xp=jnp):
    """(narrow, r0) for a walk that owns stream tokens ``[lo, hi)`` of its
    tile (tile-relative): whether its rows ``[lo * group, hi * group)`` fit
    the ``ROW_BLOCK``-row block starting at row ``r0``, a multiple of
    ``ROW_ALIGN`` inside the tile's ``rows``. The kernel calls it on SMEM
    scalars, ``count_walks`` on numpy arrays (``xp=np``). ``narrow`` is the
    Python ``False`` where the tile has no narrow block at all."""
    if rows <= ROW_BLOCK or rows % ROW_ALIGN:
        return False, 0
    r0 = xp.minimum(lo * group // ROW_ALIGN * ROW_ALIGN, rows - ROW_BLOCK)
    return hi * group - r0 <= ROW_BLOCK, r0


def interior_windows(lo, hi, q_tile: int, first_pos, win_tokens: int,
                     xp=jnp):
    """Leading context windows of ``win_tokens`` in which nothing can be
    masked, for a walk that owns stream tokens ``[lo, hi)`` of its tile
    (tile-relative) and whose tile's first token sits at absolute position
    ``first_pos``: none unless the span owns every row of the tile, else
    the windows ``w`` with ``(w + 1) * win_tokens - 1 <= first_pos``, which
    every row of the tile reaches causally and which end below the
    context's end. The kernel calls it on SMEM scalars, ``count_windows``
    on numpy arrays (``xp=np``)."""
    return xp.where((lo == 0) & (hi == q_tile),
                    (first_pos + 1) // win_tokens, 0)


def _walk_offsets(cu_q_lens, stream_tokens: int, group: int,
                  q_tile: int | None):
    """(tq, span, tile, lo, hi, start) of a dispatch's walks, on the host:
    the non-empty (tile, span) pairs, ``span`` indexing the live spans
    whose stream offsets ``start`` holds, ``lo`` / ``hi`` tile-relative."""
    cu = np.asarray(cu_q_lens, np.int64)
    tq = min(q_tile or q_tile_for(group), stream_tokens)
    start, end = cu[:-1], cu[1:]
    live = np.flatnonzero(end > start)
    start, end = start[live], end[live]
    first = start // tq
    per_span = (end - 1) // tq - first + 1  # tiles a span overlaps
    span = np.repeat(np.arange(len(start)), per_span)
    # a walk's tile: its span's first one plus its place in the span's run
    tile = first[span] + np.arange(len(span)) - np.repeat(
        np.cumsum(per_span) - per_span, per_span)
    lo = np.maximum(start[span], tile * tq) - tile * tq
    hi = np.minimum(end[span], (tile + 1) * tq) - tile * tq
    return tq, live[span], tile, lo, hi, start[span]


def count_walks(cu_q_lens, stream_tokens: int, group: int,
                q_tile: int | None = None) -> tuple[int, int]:
    """(walks, narrow walks) of one dispatch, on the host: the non-empty
    (tile, span) pairs the kernel walks for these span offsets, and how
    many of them meet ``narrow_walk``."""
    tq, span, _, lo, hi, _ = _walk_offsets(
        cu_q_lens, stream_tokens, group, q_tile)
    narrow, _ = narrow_walk(lo, hi, group, tq * group, xp=np)
    return len(span), int(np.sum(narrow))


def count_windows(cu_q_lens, context_lens, stream_tokens: int, group: int,
                  block_size: int, q_tile: int | None = None,
                  windows: int = WINDOWS, window: int = 0
                  ) -> tuple[int, int]:
    """(windows, interior windows) of one dispatch, on the host: the
    context windows of ``windows * block_size`` tokens that the kernel's
    walks stream up to each walk's causal reach, and those among them that
    a full-tile walk runs through its interior body
    (``interior_windows``, the kernel's own predicate). With a sliding
    ``window`` a walk starts at the context window that holds its first
    row's floor and has no interior."""
    tq, span, tile, lo, hi, start = _walk_offsets(
        cu_q_lens, stream_tokens, group, q_tile)
    cu = np.asarray(cu_q_lens, np.int64)
    # absolute position of a span's first token: ctx - q_len
    pos0 = np.asarray(context_lens, np.int64)[span] - (
        cu[span + 1] - cu[span])
    win_tokens = windows * block_size
    at = tile * tq - start  # tile-relative 0 as an offset into the span
    nwin = -(-(pos0 + at + hi) // win_tokens)  # reach = last row's + 1
    if window:
        floor = np.maximum(pos0 + at + lo - (window - 1), 0)
        return int(np.sum(nwin - floor // win_tokens)), 0
    interior = np.minimum(
        interior_windows(lo, hi, tq, pos0 + at, win_tokens, xp=np), nwin)
    return int(np.sum(nwin)), int(np.sum(interior))


def _lanes(x, n: int):
    """``x`` (..., LANES), equal along its last axis, at ``n`` lanes."""
    return x if x.shape[-1] == n else jnp.broadcast_to(
        x[..., 0:1], x.shape[:-1] + (n,))


def _window_heads(buf, slot, q_dtype):
    """Every head's K and V ``(win_tokens, D)`` of the window landed in
    ``buf[slot]`` ``(W, bs, 2KH, D)``, as two lists, in the type the MXU
    takes them. Where a token's bf16 slab is whole ``(16, 128)`` tiles the
    heads come off the buffer by ``slab_heads``' strided 32-bit loads (the
    slab is never cut into per-head slices). Elsewhere (narrow test
    shapes, float32 caches) plain slices."""
    _, W, bs, KH2, D = buf.shape
    if (buf.dtype == jnp.bfloat16 and q_dtype == jnp.bfloat16
            and KH2 % 16 == 0 and D % 128 == 0):
        return slab_heads(buf.at[slot])
    kv = buf[slot].reshape(W * bs, KH2, D)
    if kv.dtype != q_dtype:
        kv = kv.astype(jnp.float32)
    heads = [kv[:, h, :] for h in range(KH2)]
    return heads[:KH2 // 2], heads[KH2 // 2:]


def _ragged_kernel(
    # scalar prefetch
    bt_ref,  # (S, M) SMEM — per-slot block-table rows
    cu_ref,  # (S+1,) SMEM — cumulative query-span offsets into the stream
    cl_ref,  # (S,) SMEM — total context per slot (incl. this step's span)
    tfirst_ref,  # (nt,) SMEM — first sequence overlapping each tile
    tcnt_ref,  # (nt,) SMEM — sequences overlapping each tile
    layer_ref,  # (1,) SMEM
    # inputs
    q_ref,  # (1, KH, R, D) VMEM — R = q_tile*G rows of this tile
    kv_hbm,  # (L, N, bs, 2KH, D) ANY
    # outputs
    o_ref,  # (1, KH, R, D) VMEM
    # scratch
    buf,  # (2, W, bs, 2KH, D) VMEM
    sems,  # (2, W) DMA sems
    m_ref,  # (KH, R, LANES) f32 VMEM — flash running max
    l_ref,  # (KH, R, LANES) f32 VMEM — flash running sum
    acc_ref,  # (KH, R, D) f32 VMEM — flash accumulator
    *,
    block_size: int,
    windows: int,
    q_tile: int,
    group: int,
    scale: float,
    soft_cap: float = 0.0,
    window: int = 0,
):
    t = pl.program_id(0)
    layer = layer_ref[0]
    W = windows
    bs = block_size
    win_tokens = W * bs
    _, KH, R, D = q_ref.shape
    TQ = q_tile
    first = tfirst_ref[t]
    cnt = tcnt_ref[t]
    tile0 = t * TQ

    # ONE flash-softmax state per tile, persisting ACROSS its sequences:
    # each row belongs to exactly one span, and rows outside the current
    # span get explicit zero probability (see the masked-p note below), so
    # foreign sequences never move a row's (m, l, acc)
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def seq_body(si, _):
        """Walk one sequence's paged context for the rows it owns in this
        tile."""
        s = first + si
        q_start = cu_ref[s]
        q_end = cu_ref[s + 1]
        ctx = cl_ref[s]
        q_len = q_end - q_start
        # causal reach of this sequence's LAST token in this tile — the
        # per-block DMA predicate, so the tail over-read stays one block
        last_g = jnp.minimum(q_end, tile0 + TQ) - 1
        reach = jnp.minimum(ctx, ctx - q_len + (last_g - q_start) + 1)
        # empty spans (inactive slots, seqs not in this step) skip the
        # whole context walk
        reach = jnp.where(q_len > 0, reach, 0)
        nwin = pl.cdiv(reach, win_tokens)
        w0 = 0
        if window:
            # the floor of the span's FIRST row in this tile: no row of
            # the walk sees a key below it
            first_g = jnp.maximum(q_start, tile0)
            floor = jnp.maximum(
                ctx - q_len + (first_g - q_start) - (window - 1), 0)
            w0 = floor // win_tokens

        def dma(slot, w, j):
            bid = bt_ref[s, w * W + j]
            return pltpu.make_async_copy(
                kv_hbm.at[layer, bid], buf.at[slot, j], sems.at[slot, j]
            )

        def block_active(w, j):
            active = w * win_tokens + j * bs < reach
            if window:  # a block wholly below the floor is not fetched
                active &= w * win_tokens + (j + 1) * bs > floor
            return active

        def issue(slot, w):
            for j in range(W):
                @pl.when(block_active(w, j))
                def _():
                    dma(slot, w, j).start()

        def walk(rows, r0):
            """Stream the context past tile rows [r0, r0 + rows): the
            whole tile, or the block that covers a short span."""
            rs = pl.ds(r0, rows)
            q = q_ref[0, :, rs, :].astype(jnp.float32)  # (KH, rows, D)
            # row r is stream token g = t*TQ + r//G (rows ordered
            # (token, g))
            g_idx = tile0 + (r0 + jax.lax.broadcasted_iota(
                jnp.int32, (1, rows, 1), 1
            )) // group  # (1, rows, 1)
            row_in = (g_idx >= q_start) & (g_idx < q_end)
            # absolute position of each owned query token; garbage
            # elsewhere (masked by row_in)
            qpos = ctx - q_len + (g_idx - q_start)

            issue(jax.lax.rem(w0, 2) if window else 0, w0)

            def win_body(w, _):
                m, l = m_ref[:, rs, 0:1], l_ref[:, rs, 0:1]
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
                            q[h], k_h, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        )
                    )  # (rows, T)
                sc = jnp.stack(s_heads) * scale  # (KH, rows, T)
                if soft_cap:  # Gemma-2 score capping, before masking
                    sc = soft_cap * jnp.tanh(sc / soft_cap)
                kvpos = w * win_tokens + jax.lax.broadcasted_iota(
                    jnp.int32, (1, 1, win_tokens), 2
                )
                valid = row_in & (kvpos <= qpos) & (kvpos < ctx)
                if window:
                    valid &= kvpos > qpos - window
                sc = jnp.where(valid, sc, NEG_INF)

                m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                # masked-p: a row NOT owned by this sequence has every
                # score at NEG_INF. If that row is still untouched (m ==
                # NEG_INF), exp(sc - m_new) = exp(0) = 1 would inflate its
                # l by T per window — so invalid lanes are zeroed
                # EXPLICITLY rather than through the exp underflow the
                # decode kernel relies on.
                p = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
                l_ref[:, rs, 0:1] = l * alpha + jnp.sum(
                    p, axis=-1, keepdims=True)
                m_ref[:, rs, 0:1] = m_new
                # blocks past `reach` were never DMA'd: zero their V rows
                # — 0 x NaN = NaN would poison the accumulator through
                # masked-out weights
                vpos = w * win_tokens + jax.lax.broadcasted_iota(
                    jnp.int32, (win_tokens, 1), 0)
                vvalid = vpos < reach
                if window:  # nor were the blocks below the floor
                    vvalid &= vpos >= floor // bs * bs
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
                    )  # (rows, D)
                acc_ref[:, rs, :] = (
                    acc_ref[:, rs, :] * alpha + jnp.stack(acc_heads))
                return 0

            n_int = w0
            if rows == R and not window:
                def interior_body(w, _):
                    """A window nothing can mask, for the whole tile: every
                    block of it was fetched, every row reaches every key. Head
                    by head, operands as stored (bf16 in, float32 out of the
                    MXU, the weights rounded to the cache's type as the MXU
                    rounds them in ``win_body``), the state lane-replicated so
                    that no update broadcasts along lanes."""
                    slot = jax.lax.rem(w, 2)

                    @pl.when(w + 1 < nwin)
                    def _():
                        issue(jax.lax.rem(w + 1, 2), w + 1)

                    for j in range(W):
                        dma(slot, w, j).wait()

                    k, v = _window_heads(buf, slot, q_ref.dtype)
                    for h in range(KH):
                        sc = jax.lax.dot_general(
                            q_ref[0, h].astype(k[h].dtype), k[h],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        ) * scale  # (R, T)
                        if soft_cap:
                            sc = soft_cap * jnp.tanh(sc / soft_cap)
                        m = m_ref[h]
                        m_new = jnp.maximum(
                            m, jnp.max(sc, axis=-1, keepdims=True))
                        alpha = jnp.exp(m - m_new)
                        p = jnp.exp(sc - _lanes(m_new, win_tokens))
                        # a window as wide as the state adds its weights lane
                        # by lane: the row sum is taken once, after the loop
                        l_ref[h] = l_ref[h] * alpha + (
                            p if win_tokens == LANES
                            else jnp.sum(p, axis=-1, keepdims=True))
                        m_ref[h] = m_new
                        acc_ref[h] = acc_ref[h] * _lanes(alpha, D) + (
                            jax.lax.dot_general(
                                p.astype(v[h].dtype), v[h],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32))
                    return 0

                # a span that owns the whole tile is the only one to touch
                # its state, so the interior body finds (m, l) as the
                # kernel's first lines left them, in every lane
                n_int = jnp.minimum(nwin, interior_windows(
                    jnp.maximum(q_start, tile0) - tile0,
                    jnp.minimum(q_end, tile0 + TQ) - tile0, TQ,
                    ctx - q_len + (tile0 - q_start), win_tokens))
                jax.lax.fori_loop(0, n_int, interior_body, 0)
                if win_tokens == LANES:
                    @pl.when(n_int > 0)
                    def _():
                        l_ref[...] = jnp.broadcast_to(
                            jnp.sum(l_ref[...], axis=-1, keepdims=True),
                            l_ref.shape)
            jax.lax.fori_loop(n_int, nwin, win_body, 0)

        # the rows this span owns in the tile decide the block it pays for
        narrow, r0 = narrow_walk(
            jnp.maximum(q_start, tile0) - tile0,
            jnp.minimum(q_end, tile0 + TQ) - tile0, group, R,
        )
        live = nwin > 0
        if narrow is False:  # a tile of at most ROW_BLOCK rows
            pl.when(live)(lambda: walk(R, 0))
        else:
            pl.when(live & narrow)(
                lambda: walk(ROW_BLOCK, pl.multiple_of(r0, ROW_ALIGN)))
            pl.when(live & jnp.logical_not(narrow))(lambda: walk(R, 0))
        return 0

    jax.lax.fori_loop(0, cnt, seq_body, 0)
    # rows owned by no sequence (tail padding) kept l = 0 → output 0
    o_ref[0] = (
        acc_ref[...] / jnp.maximum(l_ref[:, :, 0:1], 1e-30)
    ).astype(o_ref.dtype)


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


@functools.partial(  # one trace for like layers: see the decode kernel's
    jax.jit,
    static_argnames=("q_tile", "windows", "interpret", "soft_cap", "window"))
def ragged_paged_attention_pallas(
    q: jnp.ndarray,  # (T, H, D) packed query stream
    kv_cache: jnp.ndarray,  # (L, N, bs, 2KH, D)
    block_tables: jnp.ndarray,  # (S, M) per-slot block rows
    cu_q_lens: jnp.ndarray,  # (S+1,) int32 cumulative span offsets
    context_lens: jnp.ndarray,  # (S,) int32 total context per slot
    layer_idx: jnp.ndarray | int = 0,
    q_tile: int | None = None,  # default: q_tile_for(G)
    windows: int = WINDOWS,
    interpret: bool = False,
    soft_cap: float = 0.0,
    window: int = 0,  # row t sees rows t - window + 1 .. t; 0 = all
) -> jnp.ndarray:
    T, H, D = q.shape
    L, N, bs, KH2, _ = kv_cache.shape
    KH = KH2 // 2
    G = H // KH
    TQ = min(q_tile or q_tile_for(G, KH), T)
    Tp = -(-T // TQ) * TQ
    if Tp != T:  # tail-pad the stream to a tile multiple (rows → zeros)
        q = jnp.pad(q, ((0, Tp - T), (0, 0), (0, 0)))
    nt = Tp // TQ
    R = TQ * G

    tfirst, tcnt = tile_metadata(cu_q_lens, nt, TQ)
    # head-major, rows ordered (token, g): (Tp, H, D) -> (nt, KH, TQ*G, D)
    q_rows = (
        q.reshape(nt, TQ, KH, G, D).transpose(0, 2, 1, 3, 4)
        .reshape(nt, KH, R, D)
    )
    layer_arr = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((1, KH, R, D), lambda t, *_: (t, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, KH, R, D), lambda t, *_: (t, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, windows, bs, KH2, D), kv_cache.dtype),
            pltpu.SemaphoreType.DMA((2, windows)),
            pltpu.VMEM((KH, R, LANES), jnp.float32),
            pltpu.VMEM((KH, R, LANES), jnp.float32),
            pltpu.VMEM((KH, R, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _ragged_kernel, block_size=bs, windows=windows, q_tile=TQ,
        group=G, scale=D**-0.5, soft_cap=soft_cap,
        **({"window": window} if window else {}),
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((nt, KH, R, D), q.dtype),
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
        out.reshape(nt, KH, TQ, G, D).transpose(0, 2, 1, 3, 4)
        .reshape(Tp, H, D)[:T]
    )
