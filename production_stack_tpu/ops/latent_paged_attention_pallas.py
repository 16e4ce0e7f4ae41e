"""Latent paged attention (MLA) — ONE Pallas call a cache layer and
dispatch, named ``%latent_paged_attention`` in both step programs, with
THREE bodies and two forms of the same scores.

Which body a call takes follows its shape and nothing else:

- the RAGGED program's call (``latent_paged_attention_pallas``: a packed
  stream, spans by ``cu_q_lens``) walks the stream in tiles, ABSORBED
  (``_absorbed_tile``: decode rows and short spans, one flash state a tile
  that its overlapping spans take turns in), and scores a span of
  ``EXPAND_ROWS`` query rows or more in this step EXPANDED, the published
  form (``_expanded_heads``), in the same ``pallas_call``;
- the DECODE program's call (``latent_decode_attention_pallas``: a
  ``(B, H, lanes)`` query, a slot a row, no spans to describe) takes the
  DECODE body (``_decode_cell``), absorbed too: several sequences a grid
  cell, a flash state each, 512-row windows, a mask only where a context
  ends. The bottom of this docstring says why one-token spans have a body
  of their own.

ABSORBED (all of this docstring down to "EXPANDED").

The pool holds one row a token and cache layer, ``(L, N, bs, lanes)``: the
latent ``c`` (the first ``value_dim`` lanes), the rotated key every head
shares, zeros up to whole 128-lane tiles. All ``H`` query heads of a token
score the SAME row over all its lanes and take its first ``value_dim``
lanes as the value (KH = 1, G = H, keys and values the same bytes), so a
window of the context is two matmuls for a whole tile: ``(R, lanes) x
(lanes, T)`` and ``(R, T) x (T, value_dim)`` with ``R = q_tile * H`` rows.
No per-head loop and no slab slicing.

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

EXPANDED. The absorbed form spends 2 * (2 * value_dim + rope) operations a
(query, context row) pair and head where the published form spends
2 * (nope + rope + v): 2176 against 640 at the published widths. Expanding
a context row to one head's key ``[W_UK_h c; r]`` and value ``W_UV_h c``
costs 2 * value_dim * (nope + v) operations, shared by every query row of
the span: past ~170 rows the published form is the cheaper one, and a
2048-row chunk pays the expansion back twelve times. So a call that is
given the heads' own queries and ``W_UK`` / ``W_UV`` (``expand``: the
ragged program) scores its long spans that way, in the SAME ``pallas_call``:
the grid's first steps are the stream's tiles as above, which skip the long
spans, and its last ``H / EXPAND_HEADS`` steps own a block of heads each,
whose queries for the WHOLE stream sit in VMEM. Such a step walks each long
span's context once in windows of ``EXPAND_WINDOWS`` blocks; a window is
fetched once, expanded once a head (two ``(rows, value_dim) x (value_dim,
128)`` products, rounded once to the cache's type as a checkpoint's own
``kv_b_proj`` output is), and scored by the span's query rows in blocks of
``EXPAND_Q_ROWS``, those above the diagonal skipped, those nothing can mask
without a mask; one flash state a (head, stream row). The form follows what
the kernel observes, a span's query rows in this step, and nothing else.

DECODE. A decode step is 64 one-token spans of 1-4 k rows each, and what
it costs is the rows' bytes. The stream's tile spent 0.70 ms a call there
where the bytes cost 0.22 (Kimi-Linear's cell, H 32; PERF.md section 6,
PR 60): it walks a tile's 16 spans one after another, each walk's first
window hidden by nothing; a window is 128 rows, ~1,100 a call, each with
eight guarded starts and waits; every window of a one-token walk runs
masked; the state is read and written back a window. ``_decode_cell``
keeps the arithmetic (``_flash_update``) and none of the control: a cell's
sequences each have their own state and landing buffers; a window is
``DECODE_WINDOWS`` blocks whose copies signal one semaphore, waited once;
the window a context ends in is the only masked one; and where a window
and the next are both whole, the next one's 32 starts stand unguarded in
the block of code that scores this one, because the call is bound by the
scalar core's descriptor work (~9 k block copies a call at ~30 ns:
0.30 ms, the products 0.24, the bytes 0.23) and the scheduler can lay that
beside the products only inside one block. 0.82 -> 0.38 ms a call at fixed
inputs, 232 -> 507 GB/s of stored rows. The ragged program's decode rows
still walk the tile: a stream tile's one state cannot be a sequence's.
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
# a span of this many query rows or more in a step is scored expanded:
# break-even ~170 rows by operations and 190-250 on the chip (one span at
# 4096 of context, absorbed / expanded ms a call: 192 rows 5.09 / 5.58, 256
# rows 6.11 / 5.45, 512 rows 10.04 / 5.17; PERF.md section 6, PR 51).
# engine/tracing.py LatentCounters counts by it too
EXPAND_ROWS = 256
# the expanded form's blocking, from the same fixed inputs (a 2048-row chunk
# at 6144 of context, ms a call): query rows scored against a window at once
# (128 / 256 / 512 / 1024: 23.2 / 15.5 / 13.8 / 13.6: the MXU holds a
# 128 x 128 tile of one operand while the other's rows stream past it),
# KV blocks a window holds (512 rows at block 16; 1024 rows gain 2 % at 6 k
# and lose 8 % on a fresh chunk), heads a grid step owns (1 / 2 / 4: 16.5 /
# 15.5 / 14.8: each step walks the context again). With them the call asks
# for 42.64 MiB of scoped VMEM (tests/test_kernel_names_v5e.py)
EXPAND_Q_ROWS = 512
EXPAND_WINDOWS = 32
EXPAND_HEADS = 4


def _latent_kernel(*refs, **static):
    """The stream's tiles alone, every span absorbed (``expand`` None): no
    program's call since the decode program has a body of its own; the
    tests hold the tile body against the XLA form through it."""
    _absorbed_tile(pl.program_id(0), *refs, **static)


def _absorbed_tile(
    t,  # the stream's tile
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
    buf,  # (2, >= W, bs, lanes) VMEM
    sems,  # (2, >= W) DMA sems
    m_ref,  # (R, LANES) f32 — flash running max, lane 0
    l_ref,  # (R, LANES) f32 — flash running sum, lane 0
    acc_ref,  # (R, value_dim) f32 — flash accumulator
    *,
    block_size: int,
    windows: int,
    q_tile: int,
    heads: int,
    scale: float,
    expand_rows: int | None = None,
):
    """One tile of the stream, absorbed. ``expand_rows``: spans of that
    many query rows or more are another grid step's (``_expanded_heads``)
    and walk nothing here."""
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
            rs = (pl.ds(r0, rows),)
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

                k = buf[slot, :W].reshape(win_tokens, lanes)
                sc = jax.lax.dot_general(
                    q_ref[(0, *rs, slice(None))], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale  # (rows, T)
                valid = None
                v = k[:, :V]
                if masked:
                    kvpos = w * win_tokens + jax.lax.broadcasted_iota(
                        jnp.int32, (1, win_tokens), 1)
                    valid = row_in & (kvpos <= qpos) & (kvpos < ctx)
                    # blocks past the reach were never fetched, and
                    # 0 x NaN = NaN
                    v = jnp.where(
                        w * win_tokens + jax.lax.broadcasted_iota(
                            jnp.int32, (win_tokens, 1), 0) < reach,
                        v, jnp.zeros_like(v))
                _flash_update(m_ref, l_ref, acc_ref, rs, sc, v, valid)
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
        if expand_rows is not None:
            live &= q_len < expand_rows
        one = hi - lo == 1
        pl.when(live & one)(lambda: walk(H, pl.multiple_of(lo * H, H)))
        pl.when(live & jnp.logical_not(one))(lambda: walk(R, 0))
        return 0

    jax.lax.fori_loop(0, cnt, seq_body, 0)
    # rows no span owns (tail padding, idle slots) kept l = 0: output 0
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, 0:1], 1e-30)
                ).astype(o_ref.dtype)


def _flash_update(m_ref, l_ref, acc_ref, rs, sc, v, valid):
    """One window's scores ``sc`` (rows, T) float32 into the flash state of
    rows ``rs`` (an index tuple into the three refs), its values ``v``
    (T, width). ``valid``: the (rows, T) mask, None where nothing can be
    masked."""
    m = m_ref[(*rs, slice(0, 1))]
    if valid is not None:
        sc = jnp.where(valid, sc, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(sc - m_new)
    if valid is not None:
        # a row this span does not own has every score at NEG_INF:
        # exp(sc - m_new) would be 1 there, so the masked weights are
        # zeroed explicitly
        p = jnp.where(valid, p, 0.0)
    l_ref[(*rs, slice(0, 1))] = l_ref[(*rs, slice(0, 1))] * alpha + jnp.sum(
        p, axis=-1, keepdims=True)
    m_ref[(*rs, slice(0, 1))] = m_new
    acc_ref[(*rs, slice(None))] = (
        acc_ref[(*rs, slice(None))] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))


def _expanded_heads(
    bt_ref, cu_ref, cl_ref, layer_ref,
    qx_ref,  # (HB, T, nope + rope lanes) VMEM — the heads' own queries
    kv_hbm,
    wuk_ref,  # (HB, value_dim, nope) VMEM
    wuv_ref,  # (HB, value_dim, v) VMEM
    ox_ref,  # (HB, T, v) VMEM
    buf,  # (2, >= WX, bs, lanes) VMEM
    sems,  # (2, >= WX) DMA sems
    mx_ref, lx_ref,  # (HB, T, LANES) f32 — flash max and sum, lane 0
    accx_ref,  # (HB, T, v) f32
    kx_ref,  # (WX * bs, nope + rope lanes) — a window's keys of one head
    vx_ref,  # (WX * bs, v) — its values
    *,
    block_size: int,
    windows: int,
    q_rows: int,
    expand_rows: int,
    scale: float,
):
    """The long spans of the stream for ``HB`` heads, in the published form:
    head h's key ``[W_UK_h c; r]``, its value ``W_UV_h c``."""
    HB = qx_ref.shape[0]
    C, Dn = wuk_ref.shape[1:]
    W, bs, QB = windows, block_size, q_rows
    Tw = W * bs
    lanes = buf.shape[-1]
    layer = layer_ref[0]

    mx_ref[...] = jnp.full(mx_ref.shape, NEG_INF, jnp.float32)
    lx_ref[...] = jnp.zeros(lx_ref.shape, jnp.float32)
    accx_ref[...] = jnp.zeros(accx_ref.shape, jnp.float32)

    def walk(s):
        q_start = cu_ref[s]
        q_end = cu_ref[s + 1]
        ctx = cl_ref[s]  # the span's last row reaches all of it
        past = ctx - (q_end - q_start)
        nwin = pl.cdiv(ctx, Tw)
        b_hi = (q_end - 1) // QB + 1

        def dma(slot, w, j):
            return pltpu.make_async_copy(
                kv_hbm.at[layer, bt_ref[s, w * W + j]], buf.at[slot, j],
                sems.at[slot, j])

        def each_block(w, do):
            def body(j, _):
                do(j)
                return 0

            jax.lax.fori_loop(
                0, jnp.minimum(W, pl.cdiv(ctx - w * Tw, bs)), body, 0)

        def issue(slot, w):
            each_block(w, lambda j: dma(slot, w, j).start())

        issue(0, 0)

        def win_body(w, _):
            slot = jax.lax.rem(w, 2)
            k0 = w * Tw

            @pl.when(w + 1 < nwin)
            def _():
                issue(1 - slot, w + 1)

            each_block(w, lambda j: dma(slot, w, j).wait())

            @pl.when(k0 + Tw > ctx)
            def _():
                # rows past the context were never fetched; they are
                # expanded with the rest, and 0 x NaN = NaN
                fetched = buf[slot, :W]
                row = (jax.lax.broadcasted_iota(jnp.int32, fetched.shape, 0)
                       * bs
                       + jax.lax.broadcasted_iota(jnp.int32, fetched.shape, 1))
                buf[slot, :W] = jnp.where(row < ctx - k0, fetched,
                                          jnp.zeros_like(fetched))

            rows = buf[slot, :W].reshape(Tw, lanes)
            c = rows[:, :C]
            kx_ref[:, Dn:] = rows[:, C:]  # the key every head shares
            # the first query block a row of which reaches this window
            b_lo = jnp.maximum(q_start, k0 - past + q_start) // QB

            def head_body(h, _):
                kx_ref[:, :Dn] = jnp.dot(
                    c, wuk_ref[h], preferred_element_type=jnp.float32
                ).astype(kx_ref.dtype)
                vx_ref[...] = jnp.dot(
                    c, wuv_ref[h], preferred_element_type=jnp.float32
                ).astype(vx_ref.dtype)

                def q_body(b, _):
                    r0 = pl.multiple_of(b * QB, QB)
                    rs = (h, pl.ds(r0, QB))
                    sc = jax.lax.dot_general(
                        qx_ref[(*rs, slice(None))], kx_ref[...],
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
                    first_pos = past + (r0 - q_start)
                    # every row the span's, and sees the window whole
                    interior = ((r0 >= q_start) & (r0 + QB <= q_end)
                                & (k0 + Tw - 1 <= first_pos))

                    @pl.when(interior)
                    def _():
                        _flash_update(mx_ref, lx_ref, accx_ref, rs, sc,
                                      vx_ref[...], None)

                    @pl.when(jnp.logical_not(interior))
                    def _():
                        g_idx = r0 + jax.lax.broadcasted_iota(
                            jnp.int32, (QB, 1), 0)
                        kvpos = k0 + jax.lax.broadcasted_iota(
                            jnp.int32, (1, Tw), 1)
                        valid = ((g_idx >= q_start) & (g_idx < q_end)
                                 & (kvpos <= first_pos + g_idx - r0))
                        _flash_update(mx_ref, lx_ref, accx_ref, rs, sc,
                                      vx_ref[...], valid)

                    return 0

                jax.lax.fori_loop(b_lo, b_hi, q_body, 0)
                return 0

            jax.lax.fori_loop(0, HB, head_body, 0)
            return 0

        jax.lax.fori_loop(0, nwin, win_body, 0)

    def span_body(s, _):
        pl.when(cu_ref[s + 1] - cu_ref[s] >= expand_rows)(lambda: walk(s))
        return 0

    jax.lax.fori_loop(0, cl_ref.shape[0], span_body, 0)
    # rows of no long span kept l = 0: they read 0 here, and the caller
    # takes them from the absorbed output
    ox_ref[...] = (accx_ref[...] / jnp.maximum(lx_ref[:, :, 0:1], 1e-30)
                   ).astype(ox_ref.dtype)


def _two_form_kernel(bt_ref, cu_ref, cl_ref, tfirst_ref, tcnt_ref, layer_ref,
                     nlong_ref, tblock_ref, q_ref, kv_hbm, qx_ref, wuk_ref,
                     wuv_ref, o_ref, ox_ref, buf, sems, m_ref, l_ref, acc_ref,
                     mx_ref, lx_ref, accx_ref, kx_ref, vx_ref, *, tiles: int,
                     absorbed: dict, expanded: dict):
    """Grid steps [0, tiles): the stream's tiles, absorbed, short spans
    only; the steps behind them: a block of heads each, the long spans
    expanded. A tile whose rows are all a long span's has no block of its
    own (``tblock_ref``): nothing of it is fetched, computed or written."""
    t = pl.program_id(0)

    @pl.when(tblock_ref[jnp.minimum(t, tiles - 1)] == t)
    def _():
        _absorbed_tile(t, bt_ref, cu_ref, cl_ref, tfirst_ref, tcnt_ref,
                       layer_ref, q_ref, kv_hbm, o_ref, buf, sems, m_ref,
                       l_ref, acc_ref, **absorbed)

    @pl.when((t >= tiles) & (nlong_ref[0] > 0))
    def _():
        _expanded_heads(bt_ref, cu_ref, cl_ref, layer_ref,
                        qx_ref, kv_hbm, wuk_ref, wuv_ref,
                        ox_ref, buf, sems, mx_ref, lx_ref, accx_ref, kx_ref,
                        vx_ref, **expanded)


def latent_paged_attention_pallas(
    q: jnp.ndarray,  # (T, H, lanes) packed stream of absorbed queries
    kv_cache: jnp.ndarray,  # (L, N, bs, lanes)
    block_tables: jnp.ndarray,  # (S, M) per-slot block rows
    cu_q_lens: jnp.ndarray,  # (S+1,) int32 cumulative span offsets
    context_lens: jnp.ndarray,  # (S,) int32 total context per slot
    layer_idx: jnp.ndarray | int = 0,
    *,
    value_dim: int,
    expand: tuple | None = None,
    q_tile: int = Q_TILE,
    windows: int = WINDOWS,
    expand_rows: int = EXPAND_ROWS,
    expand_q_rows: int = EXPAND_Q_ROWS,
    expand_windows: int = EXPAND_WINDOWS,
    expand_heads: int = EXPAND_HEADS,
    interpret: bool = False,
):
    """Returns (T, H, value_dim): the ragged program's call. (A decode
    step's one-token spans, a slot a row, are
    ``latent_decode_attention_pallas``'s; an idle slot's context 0 walks
    nothing and reads zeros here too.)

    ``expand``: (the heads' own queries (H, T, nope + lanes - value_dim),
    each ``[q_nope; q_rope; zeros]`` as the pool's row is ``[c; r;
    zeros]``; ``W_UK`` (H, value_dim, nope); ``W_UV`` (H, value_dim, v);
    the score scale). Spans of ``expand_rows`` query rows or more are then
    scored in the published form, and the call returns (the absorbed
    output, whose rows of such spans are not computed; the expanded one
    (H, T, v), of those rows; which rows of the stream they are (T,))."""
    T, H, lanes = q.shape
    L, N, bs, _ = kv_cache.shape
    TQ = min(q_tile, T)
    Tp = -(-T // TQ) * TQ
    if Tp != T:  # tail-pad the stream to whole tiles (rows -> zeros)
        q = jnp.pad(q, ((0, Tp - T), (0, 0), (0, 0)))
    nt = Tp // TQ
    R = TQ * H

    cu = jnp.asarray(cu_q_lens, jnp.int32)
    tfirst, tcnt = tile_metadata(cu, nt, TQ)
    prefetch = [jnp.asarray(block_tables, jnp.int32), cu,
                jnp.asarray(context_lens, jnp.int32), tfirst, tcnt,
                jnp.asarray(layer_idx, jnp.int32).reshape(1)]
    absorbed = dict(block_size=bs, windows=windows, q_tile=TQ, heads=H,
                    scale=lanes ** -0.5)

    def tile(t, *scalars):
        """The stream's tile of grid step t (behind the tiles: the last
        one); the tile before, so that nothing moves, where every row of
        it is a long span's (``tile_block``, the last scalar)."""
        t = jnp.minimum(t, nt - 1)
        return (t if expand is None else jnp.maximum(scalars[-1][t], 0),
                0, 0)

    in_specs = [
        pl.BlockSpec((1, R, lanes), tile, memory_space=pltpu.VMEM),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    out_specs = [pl.BlockSpec((1, R, value_dim), tile,
                              memory_space=pltpu.VMEM)]
    out_shape = [jax.ShapeDtypeStruct((nt, R, value_dim), q.dtype)]
    scratch = [
        pltpu.VMEM((R, LANES), jnp.float32),
        pltpu.VMEM((R, LANES), jnp.float32),
        pltpu.VMEM((R, value_dim), jnp.float32),
    ]
    operands = [q.reshape(nt, R, lanes), kv_cache]
    if expand is None:
        kernel = functools.partial(_latent_kernel, **absorbed)
        steps, buf_blocks = nt, windows
    else:
        qx, w_uk, w_uv, scale = expand
        HB = expand_heads if H % expand_heads == 0 else 1
        QB = min(expand_q_rows, T)
        Tx = -(-T // QB) * QB
        if Tx != T:
            qx = jnp.pad(qx, ((0, 0), (0, Tx - T), (0, 0)))
        DQ, Dv = qx.shape[-1], w_uv.shape[-1]
        is_long = cu[1:] - cu[:-1] >= expand_rows
        # a token's slot, as the kernel's walk has it, and whether its span
        # is long; a tile's own block, or for a tile of such rows alone the
        # last one before it that has one (-1: none has)
        slot = jnp.clip(jnp.searchsorted(
            cu, jnp.arange(Tp, dtype=jnp.int32), side="right",
            method="compare_all") - 1, 0, cu.shape[0] - 2)
        rows = is_long[slot] & (jnp.arange(Tp) < cu[-1])
        tile_block = jax.lax.cummax(jnp.where(
            rows.reshape(nt, TQ).all(axis=1), -1,
            jnp.arange(nt, dtype=jnp.int32)))
        prefetch += [jnp.sum(is_long, dtype=jnp.int32).reshape(1),
                     tile_block]

        def block(t, *scalars):
            """The heads' block of grid step t (before them: the first
            one; all through where no span is long, the last scalar but
            one: nothing moves)."""
            return (jnp.maximum(t - nt, 0) * (scalars[-2][0] > 0), 0, 0)

        in_specs += [
            pl.BlockSpec((HB, Tx, DQ), block, memory_space=pltpu.VMEM),
            pl.BlockSpec((HB, *w_uk.shape[1:]), block,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((HB, *w_uv.shape[1:]), block,
                         memory_space=pltpu.VMEM),
        ]
        out_specs.append(pl.BlockSpec((HB, Tx, Dv), block,
                                      memory_space=pltpu.VMEM))
        out_shape.append(jax.ShapeDtypeStruct((H, Tx, Dv), q.dtype))
        scratch += [
            pltpu.VMEM((HB, Tx, LANES), jnp.float32),
            pltpu.VMEM((HB, Tx, LANES), jnp.float32),
            pltpu.VMEM((HB, Tx, Dv), jnp.float32),
            pltpu.VMEM((expand_windows * bs, DQ), kv_cache.dtype),
            pltpu.VMEM((expand_windows * bs, Dv), kv_cache.dtype),
        ]
        operands += [qx, w_uk, w_uv]
        kernel = functools.partial(
            _two_form_kernel, tiles=nt,
            absorbed=dict(absorbed, expand_rows=expand_rows),
            expanded=dict(block_size=bs, windows=expand_windows, q_rows=QB,
                          expand_rows=expand_rows, scale=scale))
        steps, buf_blocks = nt + H // HB, max(windows, expand_windows)

    out = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(steps,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((2, buf_blocks, bs, lanes), kv_cache.dtype),
                pltpu.SemaphoreType.DMA((2, buf_blocks)),
                *scratch,
            ],
        ),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="latent_paged_attention",
    )(*prefetch, *operands)
    o_lat = out[0].reshape(Tp, H, value_dim)[:T]
    if expand is None:
        return o_lat
    return o_lat, out[1][:, :T], rows[:T]


# -- the decode program's body ------------------------------------------------

# KV blocks a window of the decode body holds: 512 rows at block 16 (ms a
# call at 64 spans of 1024-3584 rows, H 32: 128 / 256 / 512 / 1024 rows
# 0.72 / 0.48 / 0.38 / 0.39; at H 128 and on 64-256-row contexts 1024 rows
# lose 3-32 %; PERF.md section 6, PR 60)
DECODE_WINDOWS = 32
# sequences a grid cell owns at most: the cell's walk is unrolled over them
# (three bodies a sequence and slot); 8 read worse than 4 everywhere, and
# at 128 heads seven times worse
DECODE_SEQS = 4
# what a cell's sequences may hold of VMEM together: an eighth of the call's
# ``VMEM_LIMIT_BYTES`` (ops/paged_attention_pallas.py
# ``_pick_seqs_per_cell``'s budget too)
DECODE_VMEM_BYTES = 8 * 2 ** 20


def _decode_seqs_per_cell(heads: int, lanes: int, value_dim: int,
                          window_rows: int, itemsize: int) -> int:
    """Sequences a grid cell of the decode body owns: the most, a power of
    two up to ``DECODE_SEQS``, whose landing buffers (two slots a
    sequence), score and weight temporaries, flash state and double-
    buffered query and output blocks fit ``DECODE_VMEM_BYTES``: 4 at 32
    heads, 2 at 128, where the fixed inputs read best too."""
    per_seq = (2 * window_rows * lanes * itemsize
               + 3 * heads * window_rows * 4
               + heads * (value_dim + 2 * LANES) * 4
               + 2 * heads * (lanes + value_dim) * itemsize)
    fit = max(DECODE_VMEM_BYTES // per_seq, 1)
    return min(1 << (int(fit).bit_length() - 1), DECODE_SEQS)


def _decode_cell(
    # scalar prefetch
    bt_ref,  # (B, M) SMEM
    cl_ref,  # (B,) SMEM — a slot's context, its query row the last; 0: dead
    layer_ref,  # (1,) SMEM
    # inputs
    q_ref,  # (SPB, H, lanes) VMEM
    kv_hbm,  # (L, N, bs, lanes) ANY
    # outputs
    o_ref,  # (SPB, H, value_dim) VMEM
    # scratch
    buf,  # (2, SPB, W, bs, lanes) VMEM
    sems,  # (2, SPB) DMA sems: every block of a sequence's window signals one
    m_ref,  # (SPB, H, LANES) f32 — flash running max, lane 0
    l_ref,  # (SPB, H, LANES) f32 — flash running sum, lane 0
    acc_ref,  # (SPB, H, value_dim) f32
    *,
    block_size: int,
    windows: int,
    scale: float,
):
    """One-token spans, ``SPB`` sequences a grid cell, each with a flash
    state of its own: the decode program's call. The cell makes as many
    steps as its longest context has windows; a step is one window of every
    sequence that still has one, the next window's copies started before
    this one is scored, per block and only as far as the context reaches. A
    sequence past its last window (a dead slot from the first) starts,
    waits for and computes nothing. The query row is its context's last, so
    only the window the context ends in is masked."""
    layer = layer_ref[0]
    W, bs = windows, block_size
    Tw = W * bs
    SPB, H, lanes = q_ref.shape
    V = o_ref.shape[-1]
    base = pl.program_id(0) * SPB

    def start(s, slot, w, j):
        pltpu.make_async_copy(
            kv_hbm.at[layer, bt_ref[base + s, w * W + j]],
            buf.at[slot, s, j], sems.at[slot, s]).start()

    def start_window(s, slot, w):
        """All of window ``w``'s blocks, unguarded and unrolled (20 ns a
        copy where a loop's are 30)."""
        for j in range(W):
            start(s, slot, w, j)

    def wait_window(s, slot):
        """One wait for the bytes of a whole window's blocks."""
        pltpu.make_async_copy(buf.at[slot, s], buf.at[slot, s],
                              sems.at[slot, s]).wait()

    def each(n, do):
        """``do(j)`` for the first ``n`` blocks of a window, where they are
        not all of it: a loop whose trip count is the predicate (a guard a
        block would be the trace and the compile of ROADMAP.md S10 item
        3)."""
        def body(j, _):
            do(j)
            return 0

        jax.lax.fori_loop(0, jnp.where(n == W, 0, n), body, 0)

    def start_some(s, slot, w, n):
        pl.when(n == W)(lambda: start_window(s, slot, w))
        each(n, lambda j: start(s, slot, w, j))

    def wait_some(s, slot, n):
        """Every block signals the window's one semaphore: a block's bytes
        a wait."""
        pl.when(n == W)(lambda: wait_window(s, slot))
        each(n, lambda j: pltpu.make_async_copy(
            kv_hbm.at[layer, 0], buf.at[slot, s, 0],
            sems.at[slot, s]).wait())

    def blocks(rows):
        """Blocks of a window that ``rows`` rows of context reach into."""
        return jnp.clip(pl.cdiv(rows, bs), 0, W)

    def update(s, slot, valid):
        k = buf[slot, s].reshape(Tw, lanes)
        sc = jax.lax.dot_general(
            q_ref[s], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (H, Tw)
        _flash_update(m_ref, l_ref, acc_ref, (s, slice(None)), sc, k[:, :V],
                      valid)

    def tail_mask(s, slot, left):
        """A window the context ends in, ``left`` rows of it: its other
        rows (never fetched, or the tail block's own) zeroed in the landing
        buffer (0 x NaN), and the mask of its scores."""
        fetched = buf[slot, s]
        row = (jax.lax.broadcasted_iota(jnp.int32, fetched.shape, 0) * bs
               + jax.lax.broadcasted_iota(jnp.int32, fetched.shape, 1))
        buf[slot, s] = jnp.where(row < left, fetched, jnp.zeros_like(fetched))
        return jax.lax.broadcasted_iota(jnp.int32, (1, Tw), 1) < left

    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    nwin = 0  # the cell's steps: its longest context's windows
    for s in range(SPB):
        ctx = cl_ref[base + s]
        nwin = jnp.maximum(nwin, pl.cdiv(ctx, Tw))
        start_some(s, 0, 0, blocks(ctx))

    def window(s, w, slot):
        """Sequence ``s`` at window ``w`` of its context, landed in
        ``slot``: static, so that the buffer read and the buffer the next
        window's copies go to are different ones to the compiler too."""
        left = cl_ref[base + s] - w * Tw  # rows from this window on

        # this window whole and the next one too: the 32 starts stand
        # unguarded in ONE block of code with the two products, and the
        # scheduler lays the scalar core's descriptor work (~30 ns a copy,
        # 0.30 ms a call: more than the products' 0.24) beside them
        @pl.when(left >= 2 * Tw)
        def _():
            wait_window(s, slot)
            start_window(s, 1 - slot, w + 1)
            update(s, slot, None)

        @pl.when((left > Tw) & (left < 2 * Tw))
        def _():
            start_some(s, 1 - slot, w + 1, blocks(left - Tw))
            wait_window(s, slot)
            update(s, slot, None)

        @pl.when((left > 0) & (left <= Tw))
        def _():
            wait_some(s, slot, blocks(left))
            update(s, slot, tail_mask(s, slot, left))

    def pair(i, _):
        for slot in range(2):
            for s in range(SPB):
                window(s, 2 * i + slot, slot)
        return 0

    jax.lax.fori_loop(0, pl.cdiv(nwin, 2), pair, 0)
    # a dead slot kept l = 0: it reads zeros
    o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[:, :, 0:1], 1e-30)
                  ).astype(o_ref.dtype)


# behind ``jax.jit`` so that the decode program's call sites (the scanned
# periods', the first and the last segments') share a trace and a lowering,
# as ops/paged_attention_pallas.py paged_decode_attention_pallas does
@functools.partial(jax.jit, static_argnames=(
    "value_dim", "windows", "seqs_per_cell", "interpret"))
def latent_decode_attention_pallas(
    q: jnp.ndarray,  # (B, H, lanes) a slot's one absorbed query row
    kv_cache: jnp.ndarray,  # (L, N, bs, lanes)
    block_tables: jnp.ndarray,  # (B, M)
    context_lens: jnp.ndarray,  # (B,) int32, the query's own row included
    layer_idx: jnp.ndarray | int = 0,
    *,
    value_dim: int,
    windows: int = DECODE_WINDOWS,
    seqs_per_cell: int | None = None,
    interpret: bool = False,
):
    """The decode program's call: one-token spans, a slot a row. Returns
    (B, H, value_dim); an idle slot (context 0) fetches nothing and reads
    zeros. The scores, the form (absorbed) and the precision are those
    ``latent_paged_attention_pallas`` gives a one-token span.
    ``seqs_per_cell``: a test's; the call works it out."""
    B, H, lanes = q.shape
    L, N, bs, _ = kv_cache.shape
    spb = min(B, seqs_per_cell or _decode_seqs_per_cell(
        H, lanes, value_dim, windows * bs,
        jnp.dtype(kv_cache.dtype).itemsize))
    Bp = -(-B // spb) * spb
    bt = jnp.asarray(block_tables, jnp.int32)
    ctx = jnp.asarray(context_lens, jnp.int32)
    if Bp != B:  # whole cells: the slots added are dead
        q = jnp.pad(q, ((0, Bp - B), (0, 0), (0, 0)))
        bt = jnp.pad(bt, ((0, Bp - B), (0, 0)))
        ctx = jnp.pad(ctx, (0, Bp - B))

    def cell(c, *_):
        return c, 0, 0

    out = pl.pallas_call(
        functools.partial(_decode_cell, block_size=bs, windows=windows,
                          scale=lanes ** -0.5),
        out_shape=jax.ShapeDtypeStruct((Bp, H, value_dim), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(Bp // spb,),
            in_specs=[
                pl.BlockSpec((spb, H, lanes), cell, memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((spb, H, value_dim), cell,
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, spb, windows, bs, lanes), kv_cache.dtype),
                pltpu.SemaphoreType.DMA((2, spb)),
                pltpu.VMEM((spb, H, LANES), jnp.float32),
                pltpu.VMEM((spb, H, LANES), jnp.float32),
                pltpu.VMEM((spb, H, value_dim), jnp.float32),
            ],
        ),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        # the ragged program's name: the trace reductions find both by it
        name="latent_paged_attention",
    )(bt, ctx, jnp.asarray(layer_idx, jnp.int32).reshape(1), q, kv_cache)
    return out[:B]
