"""Paged KV cache + paged attention correctness (fused (L,N,bs,2KH,D)
layout): XLA reference path vs dense attention, Pallas kernels vs XLA in
interpret mode, allocator semantics."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.kv_cache import (
    PrefixCachingBlockAllocator,
    slot_mapping_for,
)
from production_stack_tpu.ops.attention import dense_causal_attention
from production_stack_tpu.ops.paged_attention import (
    combine_kv,
    paged_attention,
    split_kv,
    write_kv,
)

BS = 4  # block size
KH, D, L = 2, 8, 2


def empty_cache(num_blocks, kh=KH, d=D, layers=L):
    return jnp.zeros((layers, num_blocks, BS, 2 * kh, d), jnp.float32)


def scatter_sequence(cache, layer, ks, vs, block_ids):
    T = ks.shape[0]
    slots = jnp.asarray(slot_mapping_for(block_ids, 0, T, BS))
    return write_kv(cache, jnp.int32(layer), ks, vs, slots)


def test_combine_split_roundtrip():
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.standard_normal((5, 8, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((5, 8, 16)), jnp.float32)
    for tp in (1, 2, 4):
        fused = combine_kv(k, v, tp)
        k2, v2 = split_kv(fused, tp)
        np.testing.assert_array_equal(np.asarray(k2), np.asarray(k))
        np.testing.assert_array_equal(np.asarray(v2), np.asarray(v))


def test_paged_decode_matches_dense():
    rng = np.random.default_rng(0)
    H = 4
    lens = [7, 13, 4]
    B = len(lens)
    cache = empty_cache(32)

    tables = np.zeros((B, 8), np.int32)
    all_k, all_v = [], []
    next_block = 0
    for i, Ln in enumerate(lens):
        nb = -(-Ln // BS)
        ids = list(range(next_block, next_block + nb))
        next_block += nb
        tables[i, :nb] = ids
        ks = rng.standard_normal((Ln, KH, D), dtype=np.float32)
        vs = rng.standard_normal((Ln, KH, D), dtype=np.float32)
        all_k.append(ks)
        all_v.append(vs)
        cache = scatter_sequence(cache, 1, jnp.asarray(ks), jnp.asarray(vs), ids)

    q = rng.standard_normal((B, 1, H, D), dtype=np.float32)
    out = paged_attention(
        jnp.asarray(q), cache[1],
        jnp.asarray(tables), jnp.asarray(lens, jnp.int32),
        jnp.asarray([[Ln - 1] for Ln in lens], jnp.int32),
    )
    for i, Ln in enumerate(lens):
        full_q = np.zeros((1, Ln, H, D), np.float32)
        full_q[0, -1] = q[i, 0]
        want = dense_causal_attention(
            jnp.asarray(full_q), jnp.asarray(all_k[i])[None],
            jnp.asarray(all_v[i])[None],
        )[0, -1]
        np.testing.assert_allclose(
            np.asarray(out[i, 0]), np.asarray(want), rtol=2e-5, atol=2e-5
        )


def test_paged_chunk_prefill_matches_dense():
    rng = np.random.default_rng(1)
    H = 4
    L1, L2 = 6, 5
    T = L1 + L2
    cache = empty_cache(16)
    ids = [0, 1, 2]
    ks = rng.standard_normal((T, KH, D), dtype=np.float32)
    vs = rng.standard_normal((T, KH, D), dtype=np.float32)
    cache = scatter_sequence(cache, 0, jnp.asarray(ks), jnp.asarray(vs), ids)

    qs = rng.standard_normal((T, H, D), dtype=np.float32)
    tables = jnp.asarray([[0, 1, 2, 0]], jnp.int32)
    out = paged_attention(
        jnp.asarray(qs[None, L1:]), cache[0], tables,
        jnp.asarray([T], jnp.int32),
        jnp.asarray(np.arange(L1, T, dtype=np.int32)[None]),
    )
    want = dense_causal_attention(
        jnp.asarray(qs[None]), jnp.asarray(ks[None]), jnp.asarray(vs[None])
    )[0, L1:]
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Pallas kernels (interpret mode on CPU)
# ---------------------------------------------------------------------------

def build_random_cache(rng, layers, n, kh, d, bs=BS, dtype=jnp.float32):
    return jnp.asarray(
        rng.standard_normal((layers, n, bs, 2 * kh, d)), dtype
    )


# Decode-kernel geometries: (id, kh, G, d, bs, windows, dtype, lens,
# layer, soft_cap[, window]); the id's first word is the body
# ``decode_window_body`` gives the geometry. The small float32 ones run the
# per-head body ("head"); those at KH 16 (and 32), G 1, D 128 with a bf16
# cache run the slab body (OLMoE's and Ouro's shape); grouped queries over
# a bf16 cache with 128-wide heads run the grouped body: KH 8 at G 4
# (Qwen3) and G 8 (Solar-Open2's GQA layers) and 12 cache heads at G 4
# (Phi-4-mini-flash), at the served block of 16 and window of 8 blocks.
# Each of the two bodies that read the slab as stored meets contexts that
# end mid-block, mid-window, on a window's edge and at 0, a dead slot
# inside a live cell (the sequences of a case share one grid cell unless
# it says otherwise), a cell that is dead altogether, soft_cap > 0 and
# layer_idx > 0; the grouped body also a sliding window of 512 rows whose
# floor falls mid-block, on a window's edge, at 0 and below it.
#
# Every case's cache is poisoned: the rows past each context in its tail
# block, every block past it and, under a window, every block wholly below
# the floor hold NaN, so a body that lets an unfetched row reach a product
# (0 x NaN) fails here.
#
# Tolerance. float32 cases: 2e-4, as before. bf16 cases: kernel and XLA
# reference read the same bf16 q and cache and accumulate in float32; the
# kernel's QK products are exact, its softmax weights carry 16 mantissa
# bits (a bf16 high and low part) against the reference's 24, an error of
# ~2^-17 relative before both round the output to bf16. So the two
# outputs differ by at most one bf16 step where a value sits on a
# rounding edge: 2^-8 to 2^-7 of the value (rtol 2^-7), and absolutely by
# ~2^-17 x max|v| ~ 3e-5 where values cancel near zero (atol 1e-4).
F32, BF16 = jnp.float32, jnp.bfloat16
DECODE_GEOMETRIES = [
    ("head-kh4-g2-f32", 4, 2, 16, 4, 2, F32, [9, 16, 3], 1, 0.0),
    ("head-kh2-g1-f32", 2, 1, 16, 4, 2, F32, [5, 0, 8, 13], 0, 0.0),
    ("head-kh4-g2-f32-softcap", 4, 2, 16, 4, 2, F32, [9, 16, 3], 1, 30.0),
    ("slab-kh16-mid-block-window-zero", 16, 1, 128, 16, 2, BF16,
     [37, 0, 64, 5], 1, 0.0),
    ("slab-kh16-dead-cell-long", 16, 1, 128, 16, 2, BF16,
     [0, 0, 0, 0, 97, 32, 1, 128], 0, 0.0),
    ("slab-kh16-softcap-layer2", 16, 1, 128, 16, 2, BF16,
     [50, 33, 0, 16], 2, 30.0),
    ("slab-kh16-serving-window", 16, 1, 128, 16, 8, BF16,
     [130, 7, 0, 128], 1, 0.0),
    ("slab-kh32", 32, 1, 128, 16, 2, BF16, [19, 40], 1, 0.0),
    ("head-kh16-g1-f32", 16, 1, 128, 16, 2, F32, [37, 0], 1, 0.0),
    ("grouped-kh8-g4-two-block-window", 8, 4, 128, 16, 2, BF16,
     [37, 0, 20, 64], 1, 0.0),
    # the served shapes: eight sequences a cell at KH 8, four at 12 heads
    ("grouped-kh8-g4-edges", 8, 4, 128, 16, 8, BF16,
     [37, 200, 128, 0, 256, 130, 16, 1], 1, 0.0),
    ("grouped-kh8-g4-dead-cell", 8, 4, 128, 16, 8, BF16,
     [0] * 8 + [97, 32, 1, 128, 0, 300, 5, 129], 0, 0.0),
    ("grouped-kh8-g8-softcap-layer2", 8, 8, 128, 16, 8, BF16,
     [50, 133, 0, 16, 128, 1, 0, 260], 2, 30.0),
    ("grouped-kh8-g8-dead-cell", 8, 8, 128, 16, 8, BF16,
     [0] * 8 + [0, 70, 0, 129, 256, 3, 0, 40], 1, 0.0),
    # floors at 88 and 488 (mid-block), 3, below 0, 128 (a window's edge),
    # 1 and 0
    ("grouped-kh8-g4-window512", 8, 4, 128, 16, 8, BF16,
     [600, 515, 100, 0, 1000, 640, 513, 512], 1, 0.0, 512),
    ("grouped-kh8-g8-window512", 8, 8, 128, 16, 8, BF16,
     [700, 90, 0, 530, 0, 0, 0, 0] + [0] * 8, 2, 30.0, 512),
    ("grouped-kh12-g4-edges", 12, 4, 128, 16, 8, BF16,
     [37, 200, 128, 0], 1, 0.0),
    ("grouped-kh12-g4-dead-cell-softcap", 12, 4, 128, 16, 8, BF16,
     [0, 0, 0, 0, 97, 0, 256, 1], 2, 30.0),
    ("grouped-kh12-g4-window512", 12, 4, 128, 16, 8, BF16,
     [600, 515, 0, 100, 1000, 640, 513, 512], 1, 0.0, 512),
    # query rows that do not fill 16-row blocks of whole heads: all rows
    # go past every head's tiles (Llama-3.2-3B's G 3, Qwen2-7B's KH 4, G 7)
    ("grouped-kh8-g3-all-rows", 8, 3, 128, 16, 2, BF16,
     [37, 0, 130, 64], 1, 0.0),
    ("grouped-kh4-g7-all-rows", 4, 7, 128, 16, 2, BF16,
     [20, 64, 0, 33], 0, 0.0, 24),
    ("grouped-kh16-g2", 16, 2, 128, 16, 2, BF16, [37, 0, 64, 5], 1, 0.0),
    # what keeps the per-head body: MHA under a window, a TP-4 shard's
    # four-row slab, heads of 256
    ("head-kh16-g1-bf16-window", 16, 1, 128, 16, 2, BF16,
     [37, 0, 70, 5], 1, 0.0, 24),
    ("head-kh2-g4-bf16-tp4-shard", 2, 4, 128, 16, 2, BF16,
     [37, 0, 20, 64], 1, 0.0),
    ("head-kh8-g2-d256-bf16", 8, 2, 256, 16, 2, BF16, [37, 0], 1, 30.0),
]


def _decode_tol(dtype):
    return (dict(rtol=2e-4, atol=2e-4) if dtype == F32
            else dict(rtol=2 ** -7, atol=1e-4))


def _f32(x):
    return np.asarray(jnp.asarray(x).astype(jnp.float32))


def _poisoned_cache(rng, layers, layer, lens, kh, d, bs, dtype, window=0):
    """(poisoned cache, clean cache, tables): a block of its own for every
    table entry, in shuffled order; in ``layer``, NaN in every row no walk
    may read: past each context, and in the blocks wholly below a sliding
    window's floor. The clean cache holds 0 there."""
    B = len(lens)
    M = -(-int(max(lens)) // bs) + 2
    cache = np.array(rng.standard_normal((layers, B * M, bs, 2 * kh, d)),
                     np.float32)
    tables = rng.permutation(B * M).astype(np.int32).reshape(B, M)
    unread = np.zeros(cache.shape[1:3], bool)  # (block, row)
    for b, ctx in enumerate(lens):
        pos = np.arange(M * bs).reshape(M, bs)
        unread[tables[b]] = pos >= ctx
        if window:
            floor = max(int(ctx) - window, 0)
            unread[tables[b]] |= pos // bs < floor // bs
    clean = cache.copy()
    cache[layer][unread] = np.nan
    clean[layer][unread] = 0.0
    return (jnp.asarray(cache, dtype), jnp.asarray(clean, dtype),
            jnp.asarray(tables))


@pytest.mark.parametrize(
    "geometry", [pytest.param(g, id=g[0]) for g in DECODE_GEOMETRIES])
def test_pallas_decode_matches_xla_interpret(geometry):
    from production_stack_tpu.ops.paged_attention_pallas import (
        decode_slab_path,
        decode_window_body,
        paged_decode_attention_pallas,
    )

    name, kh, G, d, bs, windows, dtype, lens, layer, soft_cap = geometry[:10]
    window = geometry[10] if len(geometry) > 10 else 0
    body = decode_window_body(kh, G, d, dtype, window)
    assert body == name.split("-")[0]
    assert decode_slab_path(kh, G, d, dtype, window) == (body != "head")
    rng = np.random.default_rng(2)
    lens = np.array(lens, np.int32)
    B = len(lens)
    cache, clean, tables = _poisoned_cache(rng, 3, layer, lens, kh, d, bs,
                                           dtype, window)
    q = jnp.asarray(rng.standard_normal((B, kh * G, d)), dtype)
    how = {"window": window} if window else {}

    got = paged_decode_attention_pallas(
        q, cache, tables, jnp.asarray(lens),
        layer, windows=windows, interpret=True, soft_cap=soft_cap, **how,
    )
    want = paged_attention(
        q[:, None], clean[layer], tables,
        jnp.asarray(lens), jnp.asarray(lens - 1)[:, None], soft_cap=soft_cap,
        **how,
    )[:, 0]
    assert got.dtype == q.dtype
    live = lens > 0
    np.testing.assert_allclose(_f32(got)[live], _f32(want)[live],
                               **_decode_tol(dtype))
    assert np.all(_f32(got)[~live] == 0)  # a dead slot's rows come out 0


def test_decode_slab_path_predicate():
    """The decode kernel's choice of body, from (KH, G, D, cache dtype,
    window) alone, per shard: the slab body for OLMoE and Ouro as served,
    the grouped body for the GQA families, the per-head body for a shard
    with fewer than 4 KV heads, other head sizes, float32 caches and MHA
    at another head count or under a window."""
    from production_stack_tpu.engine.config import ModelConfig
    from production_stack_tpu.ops.paged_attention_pallas import (
        decode_slab_path,
        decode_window_body,
    )

    def served(name, tp=1, window=0):
        cfg = ModelConfig.from_pretrained(name)
        return decode_window_body(
            cfg.cache_kv_heads // tp, cfg.q_per_kv, cfg.cache_head_dim,
            cfg.jax_dtype, window)

    assert served("olmoe-1b-7b") == served("ouro-2.6b") == "slab"
    assert served("qwen3-8b-class") == "grouped"      # KH 8, G 4
    assert served("llama-3-8b") == served("mixtral-8x7b") == "grouped"
    assert served("llama-3b-class") == "grouped"      # KH 8, G 3
    assert served("qwen2-7b-class") == "grouped"      # KH 4, G 7
    assert served("llama-3-70b", tp=2) == "grouped"   # a shard's KH 4, G 8
    assert served("llama-3-70b", tp=4) == "head"      # KH 2: a 4-row slab
    assert served("qwen3-8b-class", tp=4) == "head"
    assert served("mistral-7b-class", window=4096) == "grouped"
    assert served("olmoe-1b-7b", tp=4) == "head"      # a TP-4 shard: KH 4
    assert served("olmoe-1b-7b", window=512) == "head"
    assert served("gemma-7b-class") == "head"         # D 256
    assert served("gemma2-9b-class") == "head"        # KH 8, G 2, D 256
    assert served("phi3-mini-class") == "head"        # KH 32, G 1, D 96
    assert served("tiny-llama") == "head"             # float32, small heads
    assert served("tiny-olmoe") == served("tiny-ouro") == "head"  # G 1, f32
    assert served("tiny-qwen3") == served("tiny-phi4flash") == "head"
    for cell in ("qwen3-8b-l16", "solar-open2-250b-ep16-l8",
                 "phi-4-mini-flash-reasoning"):
        assert _cell_body(cell) == "grouped", cell
    assert _cell_body("phi-4-mini-flash-reasoning", windowed=True) == "grouped"
    assert _cell_body("olmoe-1b-7b-l8") == _cell_body("ouro-2.6b") == "slab"
    assert decode_slab_path(16, 1, 128, "bfloat16")
    assert decode_slab_path(32, 1, 128, jnp.bfloat16)
    assert decode_slab_path(8, 4, 128, jnp.bfloat16)
    assert decode_slab_path(12, 4, 128, jnp.bfloat16, 512)
    assert not decode_slab_path(16, 1, 128, jnp.bfloat16, 512)
    assert not decode_slab_path(16, 1, 128, jnp.float32)
    assert not decode_slab_path(8, 4, 128, jnp.float32)
    assert not decode_slab_path(8, 1, 128, jnp.bfloat16)
    assert not decode_slab_path(16, 1, 256, jnp.bfloat16)
    assert not decode_slab_path(8, 4, 64, jnp.bfloat16)
    assert not decode_slab_path(24, 1, 128, jnp.bfloat16)
    assert not decode_slab_path(64, 1, 128, jnp.bfloat16)  # no chip run
    assert not decode_slab_path(2, 4, 128, jnp.bfloat16)
    assert not decode_slab_path(6, 4, 128, jnp.bfloat16)   # 12 rows a token
    assert not decode_slab_path(32, 2, 128, jnp.bfloat16)  # no chip run


def _cell_body(config: str, windowed: bool = False) -> str:
    """The body a benchmark cell's decode calls take: the predicate at the
    geometry of the cell's own configuration (chipbench/configs)."""
    import pathlib

    from production_stack_tpu.engine.config import ModelConfig
    from production_stack_tpu.ops.paged_attention_pallas import (
        decode_window_body,
    )

    cfg = ModelConfig.from_pretrained(str(
        pathlib.Path(__file__).parent.parent / "chipbench" / "configs"
        / config))
    assert not windowed or cfg.sliding_window
    return decode_window_body(
        cfg.cache_kv_heads, cfg.q_per_kv, cfg.cache_head_dim, cfg.jax_dtype,
        cfg.sliding_window if windowed else 0)


def test_pallas_kv_write_matches_scatter_interpret():
    rng = np.random.default_rng(4)
    from production_stack_tpu.ops.paged_attention_pallas import (
        kv_cache_write_pallas,
    )

    kh, d, layers, N = 4, 16, 2, 8
    T = 10
    cache = build_random_cache(rng, layers, N, kh, d)
    k = jnp.asarray(rng.standard_normal((T, kh, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((T, kh, d)), jnp.float32)
    slots = np.full(T, -1, np.int32)
    slots[:7] = rng.permutation(N * BS)[:7]  # 3 padding slots skipped
    layer = 1

    want = write_kv(cache, jnp.int32(layer), k, v, jnp.asarray(slots))
    newkv = combine_kv(k, v)
    got = jax.jit(
        functools.partial(kv_cache_write_pallas, interpret=True),
        donate_argnums=(0,),
    )(cache, newkv, jnp.asarray(slots), jnp.int32(layer))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Allocator
# ---------------------------------------------------------------------------

def test_allocator_prefix_reuse_and_eviction():
    a = PrefixCachingBlockAllocator(num_blocks=8, block_size=4)
    toks = list(range(17))
    got = a.allocate_sequence(toks)
    assert got is not None
    blocks, cached = got
    assert len(blocks) == 5 and cached == 0
    a.commit_full_blocks(toks, blocks)
    a.free_blocks(blocks)

    blocks2, cached2 = a.allocate_sequence(toks)
    assert cached2 == 16
    assert blocks2[:4] == blocks[:4]
    assert a.prefix_hits >= 4
    a.free_blocks(blocks2)

    other = list(range(100, 100 + 32))
    got3 = a.allocate_sequence(other)
    assert got3 is not None
    assert len(got3[0]) == 8


def test_allocator_out_of_blocks():
    a = PrefixCachingBlockAllocator(num_blocks=2, block_size=4)
    assert a.allocate_sequence(list(range(12))) is None
    got = a.allocate_sequence(list(range(8)))
    assert got is not None
    assert a.append_block() is None


def test_slot_mapping():
    slots = slot_mapping_for([5, 9], start=2, count=4, block_size=4)
    np.testing.assert_array_equal(slots, [22, 23, 36, 37])


@pytest.mark.parametrize("kh,G,d,bs,dtype,lens", [
    pytest.param(4, 2, 16, 4, F32, [9, 21], id="kh4-g2-f32"),
    pytest.param(16, 1, 128, 16, BF16, [37, 70, 0, 16], id="slab-kh16"),
    pytest.param(16, 1, 128, 16, BF16, [1, 33], id="slab-kh16-one-token"),
    pytest.param(8, 4, 128, 16, BF16, [37, 70, 0, 16], id="grouped-kh8-g4"),
    pytest.param(12, 4, 128, 16, BF16, [1, 33], id="grouped-kh12-one-token"),
])
def test_pallas_decode_poisoned_tail_blocks_ignored(kh, G, d, bs, dtype,
                                                    lens):
    """Per-block DMA predication (r4: the roofline's 1.8x over-read fix)
    must not let NaN/Inf in never-read tail blocks reach the output: tail
    blocks past each context are poisoned and outputs must still match.
    On the slab body the poison sits where its in-place V zeroing and its
    masked (KH, tokens x KH) weights have to keep it out."""
    rng = np.random.default_rng(7)
    from production_stack_tpu.ops.paged_attention_pallas import (
        paged_decode_attention_pallas,
    )

    lens = np.array(lens, np.int32)  # partial blocks
    B, M, layers = len(lens), 8, 1
    N = B * M
    cache = np.array(build_random_cache(rng, layers, N, kh, d, bs),
                     np.float32)
    tables = np.arange(B * M, dtype=np.int32).reshape(B, M)
    # poison every block slot past each row's live context
    for b in range(B):
        live_blocks = -(-int(lens[b]) // bs)
        for m in range(live_blocks, M):
            cache[0, tables[b, m]] = np.nan
        # ...and the tail of the last partial block
        tail = int(lens[b]) % bs
        if tail:
            cache[0, tables[b, live_blocks - 1], tail:] = np.inf
    q = jnp.asarray(rng.standard_normal((B, kh * G, d)), dtype)
    got = paged_decode_attention_pallas(
        q, jnp.asarray(cache, dtype), jnp.asarray(tables),
        jnp.asarray(lens), 0, windows=2, interpret=True,
    )
    assert np.isfinite(_f32(got)).all()
    want = paged_attention(
        q[:, None],
        jnp.asarray(np.nan_to_num(cache, posinf=0.0), dtype)[0],
        jnp.asarray(tables), jnp.asarray(lens),
        jnp.asarray(lens - 1)[:, None],
    )[:, 0]
    live = lens > 0
    np.testing.assert_allclose(_f32(got)[live], _f32(want)[live],
                               **_decode_tol(dtype))
