"""Ragged paged attention: one mixed prefill+decode dispatch.

Three layers of coverage (the serving path end to end on the tiny models
is tests/test_serving_path.py):

1. ``tile_metadata`` unit arithmetic (tile → overlapping-span ranges).
2. Interpret-mode fuzz: the Pallas ragged kernel vs the XLA ragged
   reference across randomized ragged batches — mixed chunk lengths,
   empty (inactive) spans, single-token prefills, decode rows, and
   block tables at their edge widths; plus the XLA ragged reference vs
   the padded ``paged_attention`` reference per sequence.
3. Scheduler token-budget policy units (decode rows first, FCFS chunks)
   and PerfAccountant ``record_ragged`` split units.
"""

from unittest import mock

import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.kv_cache import slot_mapping_for
from production_stack_tpu.engine.perf_accounting import PerfAccountant
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.scheduler import Scheduler
from production_stack_tpu.engine.sequence import Sequence, SequenceStatus
from production_stack_tpu.ops.paged_attention import (
    paged_attention,
    ragged_paged_attention,
    write_kv,
)
from production_stack_tpu.ops import ragged_paged_attention_pallas as rpa
from production_stack_tpu.ops.ragged_paged_attention_pallas import (
    ROW_BLOCK,
    count_walks,
    count_windows,
    interior_windows,
    narrow_walk,
    ragged_paged_attention_pallas,
    tile_metadata,
)

BS = 4  # block size
KH, D, H, L = 2, 16, 4, 2


# ---- tile_metadata --------------------------------------------------------

def test_tile_metadata_basic():
    # spans 5,0,1,7,1 over q_tile=8: tile 0 covers tokens 0..7
    # (seqs 0,1,2,3), tile 1 covers 8..13 (seq 3,4)
    cu = jnp.asarray([0, 5, 5, 6, 13, 14], jnp.int32)
    first, cnt = tile_metadata(cu, num_tiles=2, q_tile=8)
    first, cnt = np.asarray(first), np.asarray(cnt)
    assert first[0] == 0 and cnt[0] == 4
    assert first[1] == 3 and cnt[1] == 2


def test_tile_metadata_tail_tiles_are_empty():
    cu = jnp.asarray([0, 3, 3, 3], jnp.int32)  # 3 live tokens, 3 slots
    first, cnt = tile_metadata(cu, num_tiles=3, q_tile=4)
    cnt = np.asarray(cnt)
    assert cnt[0] >= 1
    assert cnt[1] == 0 and cnt[2] == 0  # past the packed total


def test_tile_metadata_one_span_many_tiles():
    cu = jnp.asarray([0, 20], jnp.int32)
    first, cnt = tile_metadata(cu, num_tiles=3, q_tile=8)
    np.testing.assert_array_equal(np.asarray(first), [0, 0, 0])
    np.testing.assert_array_equal(np.asarray(cnt), [1, 1, 1])


# ---- kernel parity fuzz ---------------------------------------------------

def _build_ragged_case(rng, q_lens, ctx_lens, M, num_blocks=64, G=H // KH):
    """Scatter per-slot contexts into a fused cache; return everything the
    two ragged implementations and the padded reference need."""
    S = len(q_lens)
    H = KH * G
    cache = jnp.zeros((L, num_blocks, BS, 2 * KH, D), jnp.float32)
    tables = np.zeros((S, M), np.int32)
    next_block = 1  # keep block 0 as the shared pad target
    per_seq_kv = []
    for s in range(S):
        ctx = ctx_lens[s]
        nb = -(-ctx // BS) if ctx else 0
        assert nb <= M
        ids = list(range(next_block, next_block + nb))
        next_block += nb
        tables[s, :nb] = ids
        if ctx:
            ks = rng.standard_normal((ctx, KH, D)).astype(np.float32)
            vs = rng.standard_normal((ctx, KH, D)).astype(np.float32)
            slots = jnp.asarray(slot_mapping_for(ids, 0, ctx, BS))
            cache = write_kv(cache, jnp.int32(1), jnp.asarray(ks),
                             jnp.asarray(vs), slots)
        else:
            ks = vs = np.zeros((0, KH, D), np.float32)
        per_seq_kv.append((ks, vs))
    T = int(sum(q_lens))
    q = rng.standard_normal((T, H, D)).astype(np.float32)
    seq_ids = np.concatenate(
        [np.full(n, s, np.int32) for s, n in enumerate(q_lens)]
        or [np.zeros(0, np.int32)]
    )
    q_pos = np.concatenate(
        [np.arange(c - n, c, dtype=np.int32)
         for n, c in zip(q_lens, ctx_lens)]
        or [np.zeros(0, np.int32)]
    )
    cu = np.zeros(S + 1, np.int32)
    cu[1:] = np.cumsum(q_lens)
    return cache, tables, cu, q, seq_ids, q_pos, per_seq_kv


FUZZ_CASES = [
    # (q_lens, ctx_lens, M): mixed chunks + decode rows + empty spans
    ([5, 0, 1, 7, 1], [9, 0, 13, 7, 1], 8),
    # single-token prefills and pure decode rows
    ([1, 1, 1, 1], [1, 5, 1, 9], 4),
    # block tables at their edge width (ctx exactly fills M blocks)
    ([4, 8], [16, 8], 4),
    # one long chunk spanning several q-tiles next to an empty slot
    ([20, 0, 2], [20, 0, 6], 8),
    # all-empty except one decode row
    ([0, 1, 0], [0, 30, 0], 8),
    # speculative verify spans (1 + k drafts ending at the slot's context)
    # packed beside plain decode rows and a prefill chunk — the fused-
    # verify dispatch shape (engine/model_runner._ragged_step)
    ([5, 1, 3, 1], [9, 17, 11, 1], 8),
    # verify span crossing a block boundary next to an empty slot
    ([6, 0, 1], [10, 0, 3], 8),
]


def _row_block_cases():
    """Spans around the kernel's row block (``ROW_BLOCK`` rows, chosen per
    (tile, span) by ``narrow_walk``) at G = 1, 3, 4: (id, G, q_tile,
    q_lens, ctx_lens, narrow walks expected, soft cap). Tiles are wider
    than the block, so both paths run; contexts reach over several
    8-token windows."""
    cases = []
    for G, tq in ((1, 128), (3, 32), (4, 32)):
        fit = ROW_BLOCK // G  # tokens of the largest narrow span
        cases += [
            # a span of exactly the block (narrow) beside one a token
            # longer (full), both from an aligned row
            (f"g{G}-block-exact", G, tq, [fit, tq - fit, fit + 1],
             [fit + 9, tq - fit, fit + 20], 1, 0.0),
            # one-token spans as first and as last row of a tile, a chunk
            # between them (full), the last row's neighbour in tile 1
            (f"g{G}-first-last-row", G, tq, [1, tq - 2, 1, 1],
             [17, tq + 5, 30, 9], 3, 0.0),
            # a chunk whose first tile is full and whose last 1-3 tokens
            # fall in the next tile (narrow), decode rows on both sides:
            # their flash state must come through both walks unharmed
            (f"g{G}-chunk-tail", G, tq, [1, tq + 1, 1, 1],
             [12, tq + 14, 21, 5], 4, 0.0),
            (f"g{G}-chunk-tail3", G, tq, [2, tq + 1, 1],
             [2, tq + 1, 26], 3, 0.0),
            # a 1 + 4 verify span, empty spans between live ones; the
            # chunk behind them leaves 10 tokens to the next tile
            (f"g{G}-verify-empties", G, tq, [1, 0, 5, 0, 0, 1, 3, tq],
             [19, 0, 23, 0, 0, 8, 11, tq + 2],
             4 + (10 * G <= ROW_BLOCK), 0.0),
            # Gemma-2's score cap through both paths
            (f"g{G}-softcap", G, tq, [1, tq, 5, 1],
             [27, tq + 3, 16, 14], 4, 5.0),
        ]
    # 128 one-token spans filling a serving-size tile
    cases.append(("g4-128-decode-rows", 4, 128, [1] * 128,
                  [1 + (7 * i) % 23 for i in range(128)], 128, 0.0))
    cases.append(("g1-128-decode-rows", 1, 128, [1] * 128,
                  [1 + (5 * i) % 19 for i in range(128)], 128, 0.0))
    return cases


ROW_BLOCK_CASES = _row_block_cases()
WIN = 2 * BS  # tokens of a context window at the tests' windows=2


def _interior_cases():
    """Spans around the kernel's interior body (the leading windows of a
    walk that owns its whole tile, up to the one holding the tile's first
    token's position; ``interior_windows``) at G = 1, 4, 8 on tiles of 64
    rows, wider than ``ROW_BLOCK``: (id, G, q_tile, q_lens, ctx_lens,
    narrow walks or None, soft cap, interior windows expected). Windows
    are ``WIN`` = 8 tokens; every case reads cache layer 1 of 2."""
    cases = []
    for G, tq in ((1, 64), (4, 16), (8, 8)):
        def whole(prior, tiles):
            # a span of whole tiles behind ``prior`` tokens of context:
            # tile i starts at position prior + i * tq
            return sum((prior + i * tq + 1) // WIN for i in range(tiles))

        cases += [
            # three whole tiles over a context of many windows
            (f"g{G}-whole-tiles", G, tq, [3 * tq], [3 * tq + 40], None,
             0.0, whole(40, 3)),
            # the prior context ends mid-window: the window holding the
            # first row's reach (position 21) takes the masked body
            (f"g{G}-reach-mid-window", G, tq, [tq], [tq + 21], None, 0.0,
             2),
            # first rows at the last and the last-but-one position of a
            # window: the window is interior only for the former
            (f"g{G}-window-edge", G, tq, [tq, tq], [tq + 23, tq + 22],
             None, 0.0, 3 + 2),
            # the context ends one token into a window (8k + 1)
            (f"g{G}-one-token-tail", G, tq, [2 * tq], [2 * tq + 33], None,
             0.0, whole(33, 2)),
            # every tile shared by a chunk's tail and the next one's head:
            # long contexts, no interior window
            (f"g{G}-shared-tiles", G, tq, [tq // 2, tq, tq // 2],
             [tq // 2 + 30, tq + 50, tq // 2 + 17], None, 0.0, 0),
            # a whole tile, then a decode row and a chunk sharing the next
            # one; Gemma-2's score cap through both window bodies
            (f"g{G}-softcap", G, tq, [tq, 1, tq - 1],
             [tq + 26, 30, tq + 8], None, 5.0, 3),
            # a fresh prompt of whole tiles: the first tile has nothing
            # below its diagonal, the second the first one's tokens
            (f"g{G}-fresh-prompt", G, tq, [2 * tq], [2 * tq], None, 0.0,
             tq // WIN),
        ]
    return cases


INTERIOR_CASES = _interior_cases()


@pytest.mark.parametrize(
    "case", list(range(len(FUZZ_CASES)))
    + [pytest.param(c, id=c[0]) for c in ROW_BLOCK_CASES + INTERIOR_CASES])
def test_ragged_pallas_matches_reference(case):
    interior = None
    if isinstance(case, int):
        q_lens, ctx_lens, M = FUZZ_CASES[case]
        G, tq, narrow, cap, seed = H // KH, 8, 0, 0.0, case
    else:
        _, G, tq, q_lens, ctx_lens, narrow, cap, *interior = case
        M = max(-(-c // BS) for c in ctx_lens)
        seed = len(q_lens) + G
    rng = np.random.default_rng(seed)
    cache, tables, cu, q, seq_ids, q_pos, _ = _build_ragged_case(
        rng, q_lens, ctx_lens, M,
        num_blocks=2 + sum(-(-c // BS) for c in ctx_lens), G=G,
    )
    # the case runs the path it was written for
    if narrow is not None:
        assert count_walks(cu, int(cu[-1]), G, q_tile=tq)[1] == narrow
    if interior:
        assert count_windows(cu, ctx_lens, int(cu[-1]), G, BS, q_tile=tq,
                             windows=2)[1] == interior[0]
    want = ragged_paged_attention(
        jnp.asarray(q), cache[1], jnp.asarray(tables),
        jnp.asarray(ctx_lens, jnp.int32), jnp.asarray(seq_ids),
        jnp.asarray(q_pos), soft_cap=cap,
    )
    got = ragged_paged_attention_pallas(
        jnp.asarray(q), cache, jnp.asarray(tables),
        jnp.asarray(cu), jnp.asarray(ctx_lens, jnp.int32),
        layer_idx=1, q_tile=tq, windows=2, interpret=True, soft_cap=cap,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("G,tq", [(1, 128), (3, 32), (4, 32), (4, 128)])
def test_count_walks_equals_brute_force(G, tq):
    """The engine's host-side count (``vllm:ragged_attn_walks_total`` and
    ``..._narrow_walks_total``) is the kernel's own iteration: every
    non-empty (tile, span) overlap, judged by the kernel's predicate."""
    rng = np.random.default_rng(G * 1000 + tq)
    for draw in range(20):
        S = int(rng.integers(1, 40))
        q_lens = rng.choice([0, 1, 1, 1, 5, 8, 9, 33, 100, 300], S)
        cu = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
        T = max(int(cu[-1]), 1) + int(rng.integers(0, 50))
        TQ = min(tq, T)
        walks = narrow = 0
        first, cnt = (np.asarray(a) for a in tile_metadata(
            jnp.asarray(cu), -(-T // TQ), TQ)) if not draw else (None, None)
        for t in range(-(-T // TQ)):
            for s in range(S):  # every (tile, span) pair, no metadata
                lo = max(cu[s], t * TQ) - t * TQ
                hi = min(cu[s + 1], (t + 1) * TQ) - t * TQ
                if hi > lo:  # the span owns rows of this tile
                    walks += 1
                    # ... and the kernel's tile metadata visits the pair
                    assert draw or first[t] <= s < first[t] + cnt[t]
                    narrow += bool(narrow_walk(lo, hi, G, TQ * G, xp=np)[0])
        assert count_walks(cu, T, G, q_tile=tq) == (walks, narrow)
    # an idle dispatch holds no walk
    assert count_walks(np.zeros(5, np.int32), 64, G, q_tile=tq) == (0, 0)


@pytest.mark.parametrize("G,tq", [(1, 64), (4, 16), (4, 128), (8, 8)])
def test_count_windows_equals_brute_force(G, tq):
    """The engine's host-side count (``vllm:ragged_attn_windows_total``
    and ``..._interior_windows_total``) is the kernel's own iteration:
    every window up to a walk's causal reach, and of a walk that owns its
    whole tile those in which a per-key loop finds nothing to mask."""
    rng = np.random.default_rng(G * 1000 + tq)
    for draw in range(20):
        S = int(rng.integers(1, 12))
        q_lens = rng.choice([0, 1, 1, 5, tq - 1, tq, tq, 2 * tq, 3 * tq + 2,
                             300], S)
        ctx = q_lens + np.where(q_lens > 0, rng.integers(0, 90, S), 0)
        cu = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
        T = max(int(cu[-1]), 1) + int(rng.integers(0, 50))
        TQ = min(tq, T)
        windows = interior = 0
        for t in range(-(-T // TQ)):
            for s in range(S):
                lo = max(cu[s], t * TQ)
                hi = min(cu[s + 1], (t + 1) * TQ)
                if hi <= lo:
                    continue
                # positions of the rows the span owns in this tile
                pos = [ctx[s] - q_lens[s] + (g - cu[s])
                       for g in range(lo, hi)]
                nwin = -(-(pos[-1] + 1) // WIN)
                windows += nwin
                for w in range(nwin):
                    unmasked = all(
                        k <= p and k < ctx[s]
                        for p in pos for k in range(w * WIN, (w + 1) * WIN))
                    interior += unmasked and hi - lo == TQ
        assert count_windows(cu, ctx, T, G, BS, q_tile=tq, windows=2) == (
            windows, interior)
    assert count_windows(np.zeros(5, np.int32), np.zeros(4, np.int32), 64,
                         G, BS, q_tile=tq, windows=2) == (0, 0)


@pytest.mark.parametrize("G,tq", [(1, 64), (4, 16), (8, 8)])
@pytest.mark.parametrize("seed", range(2))
def test_ragged_pallas_interior_fuzz(seed, G, tq):
    """Span layouts around whole tiles: chunks of one to three tiles, a
    token short or long of one, decode rows and empty slots between them,
    behind prior contexts of any length."""
    rng = np.random.default_rng(700 + 10 * G + seed)
    q_lens, ctx_lens = [], []
    for _ in range(int(rng.integers(3, 8))):
        n = int(rng.choice([0, 1, tq - 1, tq, tq, 2 * tq, 3 * tq, tq + 1]))
        q_lens.append(n)
        ctx_lens.append(n + int(rng.integers(0, 60)) if n else 0)
    M = max(1, max(-(-c // BS) for c in ctx_lens))
    cache, tables, cu, q, seq_ids, q_pos, _ = _build_ragged_case(
        rng, q_lens, ctx_lens, M,
        num_blocks=2 + sum(-(-c // BS) for c in ctx_lens), G=G,
    )
    want = ragged_paged_attention(
        jnp.asarray(q), cache[1], jnp.asarray(tables),
        jnp.asarray(ctx_lens, jnp.int32), jnp.asarray(seq_ids),
        jnp.asarray(q_pos),
    )
    got = ragged_paged_attention_pallas(
        jnp.asarray(q), cache, jnp.asarray(tables),
        jnp.asarray(cu), jnp.asarray(ctx_lens, jnp.int32),
        layer_idx=1, q_tile=tq, windows=2, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )


# (KH, G, D, block, windows, q_tile): the tests' narrow shapes, whose heads
# the interior body slices out of the landed window, and the cells' three
# geometries at serving widths (128-token windows of whole (16, 128) bf16
# tiles), whose heads it gathers with strided 32-bit loads
INTERIOR_GEOMETRIES = [(2, 4, 16, 4, 2, 16), (8, 4, 128, 16, 8, 16),
                       (16, 1, 128, 16, 8, 64), (8, 8, 128, 16, 8, 8)]


@pytest.mark.parametrize("geometry", INTERIOR_GEOMETRIES,
                         ids=lambda g: "kh%d-g%d-d%d" % g[:3])
def test_interior_body_agrees_with_the_masked_body_in_bf16(geometry):
    """The same bf16 inputs through the kernel as it is and with its
    interior predicate answering "none" (every window through the masked
    body): the two bodies differ only in where they round. Both take bf16
    q, K and V and accumulate products in float32; the interior body
    rounds a window's weights p to bf16 before PV, as the MXU does for
    either body on the chip, while the interpreter's float32 product in
    the masked body does not. An output is sum(p v) / sum(p), so rounding
    each p by at most 2^-9 of itself moves it by at most 2^-9 max|v| =
    0.0088 at |v| <= 4.5 (standard normal draws), and the bf16 output adds
    half a step of 2^-8 |out| on either side: atol 0.01, rtol 2^-7. A
    wrong key, mask or head shows as O(1)."""
    kh, G, d, bs, W, tq = geometry
    rng = np.random.default_rng(kh + G)
    q_lens, ctx_lens = [3 * tq, 1, tq, 5], [3 * tq + 19 * bs + 3, 40,
                                            tq + 2 * W * bs, 5]
    blocks = [-(-c // bs) for c in ctx_lens]
    cache = jnp.asarray(rng.standard_normal(
        (2, 1 + sum(blocks), bs, 2 * kh, d)), jnp.bfloat16)
    tables = np.zeros((len(q_lens), max(blocks)), np.int32)
    ids = iter(range(1, 1 + sum(blocks)))
    for s, nb in enumerate(blocks):
        tables[s, :nb] = [next(ids) for _ in range(nb)]
    cu = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
    q = jnp.asarray(rng.standard_normal((int(cu[-1]), kh * G, d)),
                    jnp.bfloat16)
    windows, interior = count_windows(cu, ctx_lens, int(cu[-1]), G, bs,
                                      q_tile=tq, windows=W)
    assert 0 < interior < windows

    def run():
        return np.asarray(ragged_paged_attention_pallas(
            q, cache, jnp.asarray(tables), jnp.asarray(cu),
            jnp.asarray(ctx_lens, jnp.int32), layer_idx=1, q_tile=tq,
            windows=W, interpret=True).astype(jnp.float32))

    got = run()
    with mock.patch.object(
            rpa, "interior_windows",
            lambda lo, hi, q_tile, first_pos, win_tokens, xp=jnp:
            jnp.zeros_like(first_pos)):
        masked = run()
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, masked, rtol=2 ** -7, atol=0.01)
    assert np.abs(got - masked).mean() < 1e-3  # rounding, not a bias
    # and both are the attention of the reference
    seq_ids = np.repeat(np.arange(len(q_lens)), q_lens).astype(np.int32)
    q_pos = np.concatenate([np.arange(c - n, c) for n, c
                            in zip(q_lens, ctx_lens)]).astype(np.int32)
    want = ragged_paged_attention(
        q.astype(jnp.float32), cache[1].astype(jnp.float32),
        jnp.asarray(tables), jnp.asarray(ctx_lens, jnp.int32),
        jnp.asarray(seq_ids), jnp.asarray(q_pos))
    np.testing.assert_allclose(got, np.asarray(want), rtol=2 ** -7,
                               atol=0.02)


def test_interior_windows_is_none_unless_the_span_owns_the_tile():
    args = dict(q_tile=16, win_tokens=8, xp=np)
    lo, hi = np.array([0, 1, 0, 0]), np.array([16, 16, 15, 16])
    pos = np.array([40, 40, 40, 6])
    np.testing.assert_array_equal(
        interior_windows(lo, hi, first_pos=pos, **args), [5, 0, 0, 0])


@pytest.mark.parametrize("G,tq", [(1, 128), (3, 32), (4, 32)])
@pytest.mark.parametrize("seed", range(2))
def test_ragged_pallas_row_block_fuzz(seed, G, tq):
    """The randomized fuzz on tiles wider than the row block: decode rows,
    verify spans, chunks of any length and empty slots in one stream."""
    rng = np.random.default_rng(300 + 10 * G + seed)
    q_lens, ctx_lens = [], []
    for _ in range(int(rng.integers(6, 14))):
        kind = rng.integers(0, 5)
        n = (0, 1, int(rng.integers(2, 7)), int(rng.integers(7, 40)),
             int(rng.integers(tq - 3, tq + 4)))[kind]
        q_lens.append(n)
        ctx_lens.append(n + int(rng.integers(0, 20)) if n else 0)
    M = max(1, max(-(-c // BS) for c in ctx_lens))
    cache, tables, cu, q, seq_ids, q_pos, _ = _build_ragged_case(
        rng, q_lens, ctx_lens, M,
        num_blocks=2 + sum(-(-c // BS) for c in ctx_lens), G=G,
    )
    want = ragged_paged_attention(
        jnp.asarray(q), cache[1], jnp.asarray(tables),
        jnp.asarray(ctx_lens, jnp.int32), jnp.asarray(seq_ids),
        jnp.asarray(q_pos),
    )
    got = ragged_paged_attention_pallas(
        jnp.asarray(q), cache, jnp.asarray(tables),
        jnp.asarray(cu), jnp.asarray(ctx_lens, jnp.int32),
        layer_idx=1, q_tile=tq, windows=2, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("seed", range(4))
def test_ragged_pallas_randomized_fuzz(seed):
    rng = np.random.default_rng(100 + seed)
    S = int(rng.integers(2, 6))
    q_lens, ctx_lens = [], []
    for _ in range(S):
        kind = rng.integers(0, 4)
        if kind == 0:  # inactive slot
            q_lens.append(0)
            ctx_lens.append(0)
        elif kind == 1:  # decode row
            q_lens.append(1)
            ctx_lens.append(int(rng.integers(1, 25)))
        elif kind == 2:  # single-token prefill
            q_lens.append(1)
            ctx_lens.append(1)
        else:  # mid/final prefill chunk
            n = int(rng.integers(2, 12))
            q_lens.append(n)
            ctx_lens.append(n + int(rng.integers(0, 10)))
    M = max(-(-c // BS) for c in ctx_lens) + int(rng.integers(0, 2))
    M = max(M, 1)
    cache, tables, cu, q, seq_ids, q_pos, _ = _build_ragged_case(
        rng, q_lens, ctx_lens, M
    )
    if not sum(q_lens):
        pytest.skip("degenerate all-empty draw")
    want = ragged_paged_attention(
        jnp.asarray(q), cache[1], jnp.asarray(tables),
        jnp.asarray(ctx_lens, jnp.int32), jnp.asarray(seq_ids),
        jnp.asarray(q_pos),
    )
    got = ragged_paged_attention_pallas(
        jnp.asarray(q), cache, jnp.asarray(tables),
        jnp.asarray(cu), jnp.asarray(ctx_lens, jnp.int32),
        layer_idx=1, q_tile=8, windows=2, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_ragged_reference_matches_padded_reference():
    """The XLA ragged reference (the kernel's oracle) agrees with the
    padded-batch reference sequence by sequence."""
    q_lens, ctx_lens, M = FUZZ_CASES[0]
    rng = np.random.default_rng(7)
    cache, tables, cu, q, seq_ids, q_pos, _ = _build_ragged_case(
        rng, q_lens, ctx_lens, M
    )
    ragged = np.asarray(ragged_paged_attention(
        jnp.asarray(q), cache[1], jnp.asarray(tables),
        jnp.asarray(ctx_lens, jnp.int32), jnp.asarray(seq_ids),
        jnp.asarray(q_pos),
    ))
    Smax = max(q_lens)
    for s, (n, c) in enumerate(zip(q_lens, ctx_lens)):
        if not n:
            continue
        qp = np.full((1, Smax), -1, np.int32)
        qp[0, :n] = np.arange(c - n, c)
        qpad = np.zeros((1, Smax, H, D), np.float32)
        qpad[0, :n] = q[cu[s] : cu[s] + n]
        want = np.asarray(paged_attention(
            jnp.asarray(qpad), cache[1], jnp.asarray(tables[s : s + 1]),
            jnp.asarray([c], jnp.int32), jnp.asarray(qp),
        ))[0, :n]
        np.testing.assert_allclose(
            ragged[cu[s] : cu[s] + n], want, rtol=1e-6, atol=1e-6
        )


# ---- scheduler token-budget policy ----------------------------------------

def _make_sched(budget=16, max_seqs=4):
    sched = Scheduler(
        SchedulerConfig(
            max_num_seqs=max_seqs, max_num_batched_tokens=budget,
            ),
        CacheConfig(block_size=4, num_blocks=128),
        num_blocks=128, max_model_len=256,
    )
    return sched


def _seq(rid, n, t=0.0):
    return Sequence(request_id=rid, prompt_token_ids=list(range(1, n + 1)),
                    sampling=SamplingParams(max_tokens=8, ignore_eos=True),
                    arrival_time=t)


def test_unified_schedule_fcfs_budget_no_bucket_cap():
    sched = _make_sched(budget=16)
    sched.add(_seq("a", 30, t=1.0))
    sched.add(_seq("b", 5, t=2.0))
    out = sched.schedule()
    # FCFS: the whole budget goes to the older prompt, in one chunk
    assert [(sp.seq.request_id, sp.chunk_len) for sp in out.prefills] == [
        ("a", 16)
    ]
    out.prefills[0].seq.num_computed_tokens = 16  # engine dispatch advance
    out = sched.schedule()
    # remaining 14 of "a", then 2 of "b" fill the budget
    assert [(sp.seq.request_id, sp.chunk_len) for sp in out.prefills] == [
        ("a", 14), ("b", 2)
    ]


def test_unified_schedule_decode_rows_shrink_prefill_budget():
    sched = _make_sched(budget=16)
    sched.add(_seq("dec", 4, t=1.0))
    out = sched.schedule()
    assert out.prefills[0].chunk_len == 4
    dec = out.prefills[0].seq
    dec.num_computed_tokens = 4  # prefill complete → running next step
    dec.status = SequenceStatus.RUNNING
    sched.add(_seq("new", 40, t=2.0))
    out = sched.schedule()
    # the decode row claims 1 of the 16-token budget; the fresh prompt's
    # chunk fills the remaining 15
    assert out.decodes == [dec]
    assert [(sp.seq.request_id, sp.chunk_len) for sp in out.prefills] == [
        ("new", 15)
    ]


def test_unified_schedule_decode_only_step_has_no_prefills():
    sched = _make_sched(budget=16)
    sched.add(_seq("d", 4, t=1.0))
    out = sched.schedule()
    seq = out.prefills[0].seq
    seq.num_computed_tokens = 4
    seq.status = SequenceStatus.RUNNING
    out = sched.schedule()
    assert out.decodes == [seq] and not out.prefills


# ---- perf accounting: ragged split ----------------------------------------

def _tiny_model_cfg():
    return ModelConfig(
        vocab_size=64, hidden_size=8, intermediate_size=16, num_layers=2,
        num_heads=2, num_kv_heads=1, head_dim=4, dtype="bfloat16",
    )


def _accountant():
    # attn flops/token/ctx = 4*L*H*D = 64; kv bytes/token = 2*L*KH*D*2 = 32
    return PerfAccountant(_tiny_model_cfg(), param_count=1000,
                          param_bytes=2000, window=60.0,
                          peak_tflops=1e-6, peak_hbm_gbps=1e-3)


def test_record_ragged_mixed_split():
    acc = _accountant()
    acc.record_ragged(prefill_tokens=10, prefill_ctx=30, prefill_rows=2,
                      decode_seqs=4, decode_ctx=40, ts=100.0)
    assert len(acc._events) == 2
    (_, p_phase, p_flops, p_hbm, p_tok, _), (_, d_phase, d_flops, d_hbm,
                                             d_tok, _) = acc._events
    assert (p_phase, d_phase) == ("prefill", "decode")
    assert p_flops == pytest.approx(2 * 1000 * 10 + 64 * 10 * 15)
    assert p_hbm == pytest.approx(2000 + (10 + 30) * 32)
    assert p_tok == 10
    assert d_flops == pytest.approx(2 * 1000 * 4 + 64 * 40)
    # ONE fused dispatch reads the weights once: the decode share carries
    # only its KV traffic when prefill work is present
    assert d_hbm == pytest.approx((40 + 4) * 32)
    assert d_tok == 4
    assert acc._totals["prefill_tokens"] == 10
    assert acc._totals["decode_tokens"] == 4


def test_record_ragged_decode_only_pays_weights():
    acc = _accountant()
    acc.record_ragged(0, 0, 0, decode_seqs=4, decode_ctx=40, ts=100.0)
    assert len(acc._events) == 1
    _, phase, _, hbm, _, _ = acc._events[0]
    assert phase == "decode"
    assert hbm == pytest.approx(2000 + (40 + 4) * 32)


def test_record_ragged_prefill_only_and_empty():
    acc = _accountant()
    acc.record_ragged(10, 30, 2, 0, 0, ts=100.0)
    assert len(acc._events) == 1 and acc._events[0][1] == "prefill"
    acc.record_ragged(0, 0, 0, 0, 0, ts=100.0)
    assert len(acc._events) == 1  # empty dispatch records nothing
