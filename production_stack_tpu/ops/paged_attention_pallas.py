"""Pallas TPU kernels for the paged-KV serving hot path.

Two kernels over the fused cache layout ``(L, N, block_size, 2*KH, D)``
(see ops/paged_attention.py for the layout rationale):

- ``paged_decode_attention_pallas``: one grid cell per sequence; walks the
  block table in windows of W blocks, one async DMA per block moving the
  whole ``(bs, 2KH, D)`` K+V slab, double-buffered windows, flash running
  softmax batched over heads; a landed window is scored from the slab as
  it is stored (``decode_window_body``): as one (tokens x heads, D) matrix
  at one query row a KV head, head pair by head pair through strided
  32-bit loads at grouped queries; head by head from a float32 copy only
  at the geometries no served model has.
- ``kv_cache_write_pallas``: scatters T new tokens into the pool as T async
  ``(2KH, D)``-slab DMAs on a semaphore ring, in place of an XLA scatter.
  The cache is aliased input→output, so the donated pool is updated in
  place.

All kernels take the layer index as a scalar so the full multi-layer pool
never gets sliced/copied. Grid cells execute sequentially on a TensorCore —
work per cell is kept coarse (a whole sequence) and DMAs are issued in
async batches to hide latency. A prompt's attention is the ragged kernel's
(ops/ragged_paged_attention_pallas.py).

Reference context: the reference stack delegates attention kernels to vLLM
(SURVEY.md §7 step 1); these kernels are the TPU-native equivalent of its
paged-attention/FlashAttention layer (PAPERS.md: Ragged Paged Attention).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def decode_window_body(kh: int, group: int, head_dim: int, cache_dtype,
                       window: int = 0) -> str:
    """Which body scores a landed window of the decode kernel; of the
    call's per-shard geometry alone. ``"slab"`` and ``"grouped"`` read the
    window from the slab as it lies in the landing buffer, ``"head"`` from
    a float32 copy cut into per-head values. All three want nothing else
    of the caller.

    ``"slab"`` (``_slab_window``): one query row a KV head (MHA), a bf16
    cache whose K half of a token's ``(2KH, D)`` slab is whole ``(16,
    128)`` tiles, 128-wide heads, no sliding window: a window's K rows
    then *are* a ``(tokens * KH, D)`` matrix in (token, head) order, V the
    same, and every head's one query row goes past each 128-row tile of it
    in one bf16 MXU pass. KH 16 (OLMoE, Ouro) and 32.

    ``"grouped"`` (``_grouped_window``): grouped queries over a bf16 cache
    with 128-wide heads whose token slab is whole 32-bit word rows in
    fours (KH a multiple of 4: the landing buffer's ``(8, 128)`` tiles of
    row pairs), with or without a window. KH 8 at G 3, 4 and 8 (Qwen3,
    Solar-Open2's GQA layers, Llama-3.2-3B) and KH 12 at G 4
    (Phi-4-mini-flash, whose 24 rows are one and a half vector registers:
    the strided load does not care), KH 4 at G 4 (a TP-2 shard of KH 8)
    and KH 16 at G 2 are the shapes a test and a chip run have covered.

    ``"head"`` (``_head_window``): everything else: float32 caches, heads
    that are not 128 wide, a shard with fewer than 4 KV heads (TP-4 of KH
    8: 2KH = 4), MHA at another head count or under a window."""
    if head_dim != 128 or jnp.dtype(cache_dtype) != jnp.bfloat16:
        return "head"
    if group == 1:
        return "slab" if kh in (16, 32) and not window else "head"
    return "grouped" if kh in (4, 8, 12, 16) else "head"


def decode_slab_path(kh: int, group: int, head_dim: int, cache_dtype,
                     window: int = 0) -> bool:
    """Whether the decode kernel scores a window from the slab as stored:
    what ``vllm:decode_attn_slab_calls_total`` counts."""
    return decode_window_body(kh, group, head_dim, cache_dtype,
                              window) != "head"


def _flash_weights(m, l, sc):
    """The running-softmax step both window bodies share: the new row
    maxima, the old state's rescale, the window's weights and the new row
    sums, from scores whose last axis is the window's keys."""
    m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(sc - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    return m_new, alpha, p, l_new


def _head_window(q_ref, buf, slot, w, *, W, win_tokens, scale, soft_cap,
                 window=0):
    """A landed window's flash update ``(s, ctx, (m, l, acc)) -> (m, l,
    acc)`` for one of its sequences, KV head by KV head: q (KH, G, D)
    against each head's (T, D) slice of the slab. With a sliding
    ``window`` the keys below ``ctx - window`` are masked (the query sits
    at ``ctx - 1``), and their blocks were not fetched."""
    KH = q_ref.shape[1]
    kvpos = w * win_tokens + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, win_tokens), 2
    )

    def update(s, ctx, carry):
        m, l, acc = carry
        q = q_ref[s].astype(jnp.float32)  # (KH, G, D)
        kv = jnp.concatenate(
            [buf[slot, s, j] for j in range(W)], axis=0
        )  # (T, 2KH, D)
        s_heads = []
        for h in range(KH):
            k_h = kv[:, h, :].astype(jnp.float32)  # (T, D)
            s_heads.append(
                jax.lax.dot_general(
                    q[h], k_h, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )  # (G, T)
        sc = jnp.stack(s_heads) * scale  # (KH, G, T)
        if soft_cap:  # Gemma-2 score capping, before masking
            sc = soft_cap * jnp.tanh(sc / soft_cap)
        seen = kvpos < ctx
        if window:
            seen &= kvpos >= ctx - window
        sc = jnp.where(seen, sc, NEG_INF)

        m_new, alpha, p, l_new = _flash_weights(m, l, sc)
        # per-block DMA predication leaves tail blocks UNWRITTEN: their
        # V rows can be NaN/Inf, and the PV contraction sums p*v over
        # ALL T — 0 x NaN = NaN, so masked weights alone don't protect
        # the accumulator. Zero the invalid V rows explicitly.
        vpos = w * win_tokens + jax.lax.broadcasted_iota(
            jnp.int32, (win_tokens, 1), 0)
        vvalid = vpos < ctx
        if window:
            vvalid &= vpos >= ctx - window
        acc_heads = []
        for h in range(KH):
            v_h = jnp.where(
                vvalid, kv[:, KH + h, :].astype(jnp.float32), 0.0
            )  # (T, D)
            acc_heads.append(
                jax.lax.dot_general(
                    p[h], v_h, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )  # (G, D)
        acc_new = acc * alpha + jnp.stack(acc_heads)
        return m_new, l_new, acc_new

    return update


def _slab_window(q_ref, buf, slot, w, *, W, win_tokens, scale, soft_cap):
    """The same update where ``decode_slab_path`` holds, on the slab as
    stored. A token's K half is one whole bf16 tile per 16 heads, so the
    window's K is the ``(T * KH, D)`` matrix of its (token, head) rows
    with no relayout; ``q (KH, D)`` times its transpose gives every
    head's product with every row, and a row's wanted score is the one
    where the query's head is the row's own (column ``c`` belongs to
    head ``c % KH``). The flash state keeps that ``(KH, T * KH)`` form,
    off-diagonal entries masked to exactly zero weight, and PV is its
    mirror: the masked weights times the ``(T * KH, D)`` V rows. QK has
    bf16 operands and float32 accumulation (a bf16 x bf16 product is exact
    in float32); the float32 weights go past V as a bf16 high and low part
    stacked on the sublanes (16 mantissa bits where the output is rounded
    to 8), one load of each stationary V tile for both."""
    KH, D = q_ref.shape[1], q_ref.shape[2]
    bs = win_tokens // W
    cols = win_tokens * KH
    col = jax.lax.broadcasted_iota(jnp.int32, (KH, cols), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (KH, cols), 0)
    # a column's position in the context, pushed past any ctx where the
    # column is another head's: one compare masks both
    kvpos = jnp.where(col % KH == head, w * win_tokens + col // KH,
                      jnp.int32(2 ** 30))

    def update(s, ctx, carry):
        m, l, acc = carry  # (KH, 1), (KH, 1), (KH, D)
        k = buf[slot, s, :, :, 0:KH, :].reshape(cols, D)
        sc = jax.lax.dot_general(
            q_ref[s], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (KH, T * KH)
        if soft_cap:
            sc = soft_cap * jnp.tanh(sc / soft_cap)
        sc = jnp.where(kvpos < ctx, sc, NEG_INF)

        m_new, alpha, p, l_new = _flash_weights(m, l, sc)
        # _head_window's NaN rule, in place and only where it bites: a
        # fetched window's block with rows past ctx (its tail, or never
        # fetched) has those V rows zeroed in the buffer
        for j in range(W):
            first = w * win_tokens + j * bs

            @pl.when((first + bs > ctx) & (w * win_tokens < ctx))
            def _():
                tok = first + jax.lax.broadcasted_iota(
                    jnp.int32, (bs, KH, D), 0)
                v = buf[slot, s, j, :, KH:, :]
                buf[slot, s, j, :, KH:, :] = jnp.where(
                    tok < ctx, v, jnp.zeros_like(v))

        v = buf[slot, s, :, :, KH:, :].reshape(cols, D)
        p_hi = p.astype(v.dtype)
        p_lo = (p - p_hi.astype(jnp.float32)).astype(v.dtype)
        pv = jax.lax.dot_general(
            jnp.concatenate([p_hi, p_lo], axis=0), v,
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )  # (2KH, D)
        acc_new = acc * alpha + (pv[:KH] + pv[KH:])
        return m_new, l_new, acc_new

    return update


def _zero_unseen_rows(buf, slot, s, w, ctx, *, W, win_tokens, window=0):
    """Make the rows a walk did not fetch harmless where a product sums
    over them (0 x NaN = NaN): in the landing buffer, and only in the
    blocks of window ``w`` that hold any: the context's tail block, the
    blocks past it and, under a sliding ``window``, the blocks wholly below
    the floor. Nothing runs for a window that was fetched whole."""
    bs = win_tokens // W
    start = w * win_tokens
    floor = jnp.maximum(ctx - window, 0) if window else 0
    ragged = start + win_tokens > ctx
    if window:
        ragged |= start + bs <= floor

    @pl.when(ragged & (start < ctx))
    def _():
        for j in range(W):
            first = start + j * bs
            unseen = first + bs > ctx
            if window:
                unseen |= first + bs <= floor

            @pl.when(unseen)
            def _():
                # a token's bf16 rows are whole 32-bit words: cleared as such
                words = pltpu.bitcast(buf[slot, s, j], jnp.uint32)
                tok = first + jax.lax.broadcasted_iota(
                    jnp.int32, words.shape, 0)
                keep = tok < ctx
                if window:
                    keep &= first + bs > floor
                buf[slot, s, j] = pltpu.bitcast(
                    jnp.where(keep, words, jnp.uint32(0)), buf.dtype)


def slab_heads(window):
    """Every head's K and V ``(win_tokens, D)`` of a landed window, a ref
    ``(W, bs, 2KH, D)`` into a bf16 landing buffer, as two lists of bf16
    tiles. The window is read as 32-bit words, two heads a word (word row
    j of a token: heads 2j and 2j + 1): one sublane-strided load a head
    pair gathers the pair's rows of all tokens, so the slab is never cut
    into per-head slices, and a shift or a mask leaves either head as the
    high half of a float32 that converts to bf16 exactly (PR 38, for the
    ragged kernel's interior body). Any even number of word rows a token
    will do: 2KH = 24 is twelve."""
    W, bs, KH2, D = window.shape
    T = W * bs
    words = window.reshape(T * KH2, D).bitcast(jnp.uint32)
    heads = []
    for j in range(KH2 // 2):
        pair = words[pl.ds(j, T, stride=KH2 // 2), :]
        heads += [pltpu.bitcast(half, jnp.float32).astype(jnp.bfloat16)
                  for half in (pair << 16, pair & jnp.uint32(0xFFFF0000))]
    return heads[:KH2 // 2], heads[KH2 // 2:]


def _grouped_window(q_ref, buf, slot, w, *, W, win_tokens, scale, soft_cap,
                    group, window=0):
    """The same update for grouped queries (``decode_window_body``), on
    the slab as it lies in ``buf``: q ``(KH * G, D)``, row r of KV head
    ``r // G``. K and V tiles come off the landing buffer by
    ``slab_heads``; each is the stationary operand of one product, past
    which go the 16 query rows (one packed bf16 tile) that hold the head's
    own, the other heads' rows of the block dropped by a select (all rows
    where G does not divide 16). QK has bf16 operands and float32
    accumulation; the float32 weights go past V as a bf16 high and low
    part stacked on the sublanes, as in ``_slab_window``. The scores of a
    window are ``(KH * G, win_tokens)``, four vector registers at Qwen3's
    shape, so the flash state stays ``(rows, 1)``: lane-wide ``m`` and
    ``l`` read the same on the chip (PERF.md section 5).

    0 x NaN: a K row the walk did not fetch can only reach a score, and
    the ``where`` below replaces that score whatever it is; a V row
    reaches the accumulator through a zero weight, so those are cleared
    in the buffer first (``_zero_unseen_rows``). With a sliding ``window``
    the keys below ``ctx - window`` are masked and their blocks were not
    fetched."""
    R, D = q_ref.shape[1], q_ref.shape[2]
    T = win_tokens
    kvpos = w * T + jax.lax.broadcasted_iota(jnp.int32, (R, T), 1)
    step = 16 if R % 16 == 0 and 16 % group == 0 else R
    heads = -(-step // group)  # KV heads a block of query rows spans
    own_s = jax.lax.broadcasted_iota(jnp.int32, (step, T), 0) // group
    own_d = jax.lax.broadcasted_iota(jnp.int32, (step, D), 0) // group

    def update(s, ctx, carry):
        m, l, acc = carry  # (R, 1), (R, 1), (R, D)
        k, v = slab_heads(buf.at[slot, s])
        blocks = []
        for r0 in range(0, R, step):
            q = q_ref[s, r0:r0 + step, :]
            sc = None
            for i in range(heads):
                sc_h = jax.lax.dot_general(
                    q, k[r0 // group + i], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)  # (step, T)
                sc = sc_h if sc is None else jnp.where(own_s == i, sc_h, sc)
            blocks.append(sc)
        sc = jnp.concatenate(blocks, axis=0) * scale  # (R, T)
        if soft_cap:
            sc = soft_cap * jnp.tanh(sc / soft_cap)
        seen = kvpos < ctx
        if window:
            seen &= kvpos >= ctx - window
        sc = jnp.where(seen, sc, NEG_INF)

        m_new, alpha, p, l_new = _flash_weights(m, l, sc)
        p_hi = p.astype(buf.dtype)
        p_lo = (p - p_hi.astype(jnp.float32)).astype(buf.dtype)
        blocks = []
        for r0 in range(0, R, step):
            rows = slice(r0, r0 + step)
            weights = jnp.concatenate([p_hi[rows], p_lo[rows]], axis=0)
            pv = None
            for i in range(heads):
                pv_h = jax.lax.dot_general(
                    weights, v[r0 // group + i], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)  # (2 step, D)
                pv_h = pv_h[:step] + pv_h[step:]
                pv = pv_h if pv is None else jnp.where(own_d == i, pv_h, pv)
            blocks.append(pv)
        acc_new = acc * alpha + jnp.concatenate(blocks, axis=0)
        return m_new, l_new, acc_new

    return update


def _decode_kernel(
    # scalar prefetch
    bt_ref,  # (B, M) SMEM
    cl_ref,  # (B,) SMEM
    layer_ref,  # (1,) SMEM
    # inputs
    q_ref,  # (SPB, KH, G, D) VMEM — SPB sequences per grid cell
    kv_hbm,  # (L, N, bs, 2KH, D) ANY
    # outputs
    o_ref,  # (SPB, KH, G, D) VMEM
    # scratch
    buf,  # (2, SPB, W, bs, 2KH, D) VMEM
    sems,  # (2, SPB, W) DMA sems
    *,
    block_size: int,
    windows: int,
    seqs_per_cell: int,
    scale: float,
    soft_cap: float = 0.0,
    window_body: str = "head",
    group: int = 1,
    window: int = 0,
):
    """Batched paged decode attention.

    Grid cells run SEQUENTIALLY on a TensorCore, and at one sequence per
    cell a fused dispatch is 192 seqs x 28 layers x 16 steps ≈ 86k cell
    executions. Each cell therefore
    handles SPB sequences: their window DMAs are all in flight together
    (SPB x W parallel copies) and the QK^T / PV matmuls batch over the
    sequence dim — batch dims at position 0 on both operands, the layout
    Mosaic's batched matmul requires.

    The DMA walk, the flash carry and the epilogue are one; what a landed
    window computes is ``window_body``'s (the wrapper's
    ``decode_window_body``): ``_slab_window`` (q and o then come as (SPB,
    KH, D)), ``_grouped_window`` ((SPB, KH * G, D)) or ``_head_window``
    ((SPB, KH, G, D)).

    With a sliding ``window`` (not the slab body) a sequence's
    walk has a floor, ``ctx - window``: it starts at the context window
    that holds the floor (each of a cell's sequences at its own), and a
    block wholly below the floor is neither fetched nor scored."""
    cell = pl.program_id(0)
    layer = layer_ref[0]
    SPB = seqs_per_cell
    W = windows
    bs = block_size
    win_tokens = W * bs
    base = cell * SPB
    # per-cell window count: the longest context in the cell (shorter
    # sequences mask the tail; dead slots carry ctx 0)
    nwin = pl.cdiv(cl_ref[base], win_tokens)
    for s in range(1, SPB):
        nwin = jnp.maximum(nwin, pl.cdiv(cl_ref[base + s], win_tokens))

    def at(s, w):
        """The context window sequence ``s`` takes at step ``w`` of the
        cell's walk: ``w`` itself, or with a sliding ``window`` its own
        first one (the one that holds its floor) plus ``w``, so that a
        cell's sequences of different lengths all walk their last
        ``window`` rows in the same few steps."""
        return w

    if window:
        def floor(s):
            return jnp.maximum(cl_ref[base + s] - window, 0)

        def at(s, w):  # noqa: F811
            return floor(s) // win_tokens + w

        # the cell's steps: its longest walk (a dead slot's is empty)
        nwin = pl.cdiv(cl_ref[base], win_tokens) - at(0, 0)
        for s in range(1, SPB):
            nwin = jnp.maximum(
                nwin, pl.cdiv(cl_ref[base + s], win_tokens) - at(s, 0))

    def dma(slot, s, w, j):
        bid = bt_ref[base + s, at(s, w) * W + j]
        return pltpu.make_async_copy(
            kv_hbm.at[layer, bid], buf.at[slot, s, j], sems.at[slot, s, j]
        )

    # per-BLOCK predication: the DMA unit is one block (bs tokens), so a
    # sequence's tail over-read is bounded by bs, not the whole window —
    # at ctx≈150/bs=16/W=8 the old per-window predication streamed
    # ceil(150/128)*128 = 256 tokens/seq; per-block streams
    # ceil(150/16)*16 = 160 (docs/roofline.md). This kernel is HBM-bound:
    # skipped traffic is pure win. wait() uses the same predicate so waits
    # match issues exactly.
    def seq_active(s, w):
        return at(s, w) * win_tokens < cl_ref[base + s]

    def block_active(s, w, j):
        active = at(s, w) * win_tokens + j * bs < cl_ref[base + s]
        if window:  # a block wholly below the floor is not fetched
            active &= at(s, w) * win_tokens + (j + 1) * bs > floor(s)
        return active

    def issue(slot, w):
        for s in range(SPB):
            for j in range(W):
                @pl.when(block_active(s, w, j))
                def _():
                    dma(slot, s, w, j).start()

    @pl.when(nwin > 0)
    def _():
        issue(0, 0)

    landed = functools.partial(
        {"head": _head_window, "slab": _slab_window,
         "grouped": functools.partial(_grouped_window, group=group),
         }[window_body], q_ref, buf,
        W=W, win_tokens=win_tokens, scale=scale, soft_cap=soft_cap,
        **({"window": window} if window else {}))

    # per-seq tensors stay <=3D throughout (Mosaic's layout inference
    # rejects middle-dim squeezes/merges on 4D); the flash state is a flat
    # tuple of per-seq (m, l, acc) triples on the fori carry
    def body(w, carry):
        slot = jax.lax.rem(w, 2)

        @pl.when(w + 1 < nwin)
        def _():
            issue(jax.lax.rem(w + 1, 2), w + 1)

        for s in range(SPB):
            for j in range(W):
                @pl.when(block_active(s, w, j))
                def _():
                    dma(slot, s, w, j).wait()

        if window_body == "grouped":
            # every sequence's unfetched rows first: the conditional stores
            # then stand before the updates, not between them, and the
            # compiler schedules the cell's products as one block
            for s in range(SPB):
                _zero_unseen_rows(
                    buf, slot, s, at(s, w), cl_ref[base + s], W=W,
                    win_tokens=win_tokens, window=window)
        update = landed(slot, w)
        out = []
        for s in range(SPB):
            old = carry[3 * s : 3 * s + 3]
            if window:  # the sequence's own window of this step
                update = landed(slot, at(s, w))
            new = update(s, cl_ref[base + s], old)
            # a seq inactive this window skipped its DMAs: buf holds
            # unwritten bits that can be NaN/Inf, and 0 x NaN = NaN — keep
            # the old carry instead of trusting masked math
            act = seq_active(s, w)
            out += [jnp.where(act, n, o) for n, o in zip(new, old)]
        return tuple(out)

    rows = q_ref.shape[1:-1]  # (KH, G); (KH,) or (KH * G,) from the slab
    D = q_ref.shape[-1]
    init = []
    for _ in range(SPB):
        init += [
            jnp.full((*rows, 1), NEG_INF, jnp.float32),
            jnp.zeros((*rows, 1), jnp.float32),
            jnp.zeros((*rows, D), jnp.float32),
        ]
    final = jax.lax.fori_loop(0, nwin, body, tuple(init))
    for s in range(SPB):
        l, acc = final[3 * s + 1], final[3 * s + 2]
        o_ref[s] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _pick_seqs_per_cell(B: int, bs: int, KH2: int, D: int, windows: int,
                        itemsize: int) -> int:
    """Largest SPB dividing B whose double-buffered window scratch fits a
    VMEM budget (~8 MB, half the scoped limit). In VMEM a token's
    (2KH, D) slab is padded to whole sublane tiles (16 rows of bf16), so
    a head-sharded pool (2KH = 4 per shard at KH=8, TP=4) occupies four
    times its HBM bytes there."""
    budget = 8 * 1024 * 1024
    sublanes = 32 // itemsize
    rows = -(-KH2 // sublanes) * sublanes
    per_seq = 2 * windows * bs * rows * D * itemsize
    spb = max(budget // per_seq, 1)
    while spb > 1 and B % spb:
        spb -= 1
    return int(min(spb, B))


# behind ``jax.jit`` (as ``moe_grouped_matmul`` is) so that the calls of like
# layers of one program share a trace and a lowering: a stack whose layers
# are unrolled and not scanned traced and lowered each kernel once a LAYER
# (PR 59: 308 -> 27.5 s of an eight-layer start on the chip's host). The
# compiled program is the same: the call is inlined, as many instructions
# under the same names, the pool still updated in place.
@functools.partial(
    jax.jit, static_argnames=("windows", "interpret", "soft_cap", "window"))
def paged_decode_attention_pallas(
    q: jnp.ndarray,  # (B, H, D)
    kv_cache: jnp.ndarray,  # (L, N, bs, 2KH, D)
    block_tables: jnp.ndarray,  # (B, M)
    context_lens: jnp.ndarray,  # (B,)
    layer_idx: jnp.ndarray | int = 0,
    windows: int = 8,
    interpret: bool = False,
    soft_cap: float = 0.0,
    window: int = 0,  # the query sees the last ``window`` rows; 0 = all
) -> jnp.ndarray:
    B, H, D = q.shape
    L, N, bs, KH2, _ = kv_cache.shape
    KH = KH2 // 2
    G = H // KH
    body = decode_window_body(KH, G, D, kv_cache.dtype, window)
    # q heads are shard-grouped like the cache: here a single shard's view,
    # heads ordered [h0..h_{KH-1}] matching [K_0..K_{KH-1}] halves; the
    # bodies that read the slab as stored take the heads as the rows of
    # one matrix
    qshape = {"head": (KH, G, D), "slab": (KH, D), "grouped": (H, D)}[body]
    zeros = (0,) * len(qshape)
    q4 = q.reshape(B, *qshape)
    layer_arr = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    spb = _pick_seqs_per_cell(B, bs, KH2, D, windows,
                              jnp.dtype(kv_cache.dtype).itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B // spb,),
        in_specs=[
            pl.BlockSpec((spb, *qshape), lambda b, *_: (b, *zeros),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((spb, *qshape), lambda b, *_: (b, *zeros),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, spb, windows, bs, KH2, D), kv_cache.dtype),
            pltpu.SemaphoreType.DMA((2, spb, windows)),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, block_size=bs, windows=windows, seqs_per_cell=spb,
        scale=D**-0.5, soft_cap=soft_cap, window_body=body, group=G,
        **({"window": window} if window else {}),
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, *qshape), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="paged_decode_attention",
    )(block_tables, context_lens, layer_arr, q4, kv_cache)
    return out.reshape(B, H, D)


# ---------------------------------------------------------------------------
# KV write
# ---------------------------------------------------------------------------

_RING = 8


def _kv_write_kernel(
    # scalar prefetch
    slots_ref,  # (T,) SMEM — flat cache slots, -1 = skip
    layer_ref,  # (1,) SMEM
    # inputs
    newkv_ref,  # (T, 2KH, D) VMEM
    kv_hbm,  # (L, N, bs, 2KH, D) ANY (aliased to output)
    # output
    out_hbm,  # aliased kv_hbm
    # scratch
    sems,  # (RING,) DMA sems
    *,
    block_size: int,
    total: int,
):
    layer = layer_ref[0]

    def dma(i):
        slot = slots_ref[i]
        bid = slot // block_size
        off = slot - bid * block_size
        return pltpu.make_async_copy(
            newkv_ref.at[i], out_hbm.at[layer, bid, off], sems.at[i % _RING]
        )

    def body(i, _):
        @pl.when(i >= _RING)
        def _():
            @pl.when(slots_ref[i - _RING] >= 0)
            def _():
                dma(i - _RING).wait()

        @pl.when(slots_ref[i] >= 0)
        def _():
            dma(i).start()

        return 0

    jax.lax.fori_loop(0, total, body, 0)
    # drain the ring
    for r in range(max(_RING - total, 0), _RING):
        i = total - _RING + r

        @pl.when(slots_ref[i] >= 0)
        def _(i=i):
            dma(i).wait()


@functools.partial(jax.jit, static_argnames="interpret")
def kv_cache_write_pallas(
    kv_cache: jnp.ndarray,  # (L, N, bs, 2KH, D) — donated, updated in place
    newkv: jnp.ndarray,  # (T, 2KH, D) combined update (see combine_kv)
    slot_mapping: jnp.ndarray,  # (T,) int32, -1 = padding
    layer_idx: jnp.ndarray | int = 0,
    interpret: bool = False,
) -> jnp.ndarray:
    L, N, bs, KH2, D = kv_cache.shape
    T = newkv.shape[0]
    layer_arr = jnp.asarray(layer_idx, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((_RING,))],
    )
    kernel = functools.partial(_kv_write_kernel, block_size=bs, total=T)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(kv_cache.shape, kv_cache.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        input_output_aliases={3: 0},  # kv_hbm input → output buffer
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        name="kv_cache_write",
    )(slot_mapping, layer_arr, newkv, kv_cache)
