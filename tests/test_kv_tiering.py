"""Tiered KV cache (docs/kv_tiering.md): byte-accounted host tier, async
prefetch with the PREFETCHING park + commit-time safety recheck, tier chaos
drills (corrupt remote → clean miss; engine death mid-prefetch → clean
fleet), the hardened kv_server, and the router's expected-cached-prefix
scoring over scraped per-tier hit ratios."""

import asyncio
import dataclasses
import threading
import time

import numpy as np
import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.kv_offload import HostKVStore, chain_hashes
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.sequence import SequenceStatus
from production_stack_tpu.engine.weights import init_or_load
from production_stack_tpu.kv_server import KVServer
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh
from production_stack_tpu.router.hashtrie import HashTrie
from production_stack_tpu.router.protocols import EngineStats
from production_stack_tpu.router.routing import (
    PrefixAwareRouter,
    TIER_WEIGHTS,
    tier_import_weight,
)
from production_stack_tpu.testing.chaos import ChaosKVServer

GREEDY = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)


def _slab(seed: int, nbytes_scale: int = 1) -> np.ndarray:
    # (L, bs, 2KH, D) block slab; distinct content per seed
    rng = np.random.default_rng(seed)
    return rng.standard_normal((2, 4, 4, 8 * nbytes_scale)).astype(np.float32)


# ---------------------------------------------------------------------------
# HostKVStore byte accounting
# ---------------------------------------------------------------------------

def test_host_store_byte_capacity_is_authoritative():
    one = _slab(0)
    store = HostKVStore(capacity_blocks=0, block_size=4,
                        capacity_bytes=3 * one.nbytes)
    for i in range(5):
        assert store.put(1000 + i, _slab(i))
    assert len(store.store) == 3
    assert store.used_bytes == 3 * one.nbytes
    assert store.evictions == 2
    assert 0 < store.usage <= 1.0
    # oversized slab can never fit: refused, store state untouched
    big = _slab(9, nbytes_scale=8)
    assert big.nbytes > store.capacity_bytes
    assert not store.put(2000, big)
    assert store.used_bytes == 3 * one.nbytes


def test_host_store_legacy_block_capacity_fixed_by_first_slab():
    store = HostKVStore(capacity_blocks=2, block_size=4)
    assert store.capacity_bytes == 0  # not fixed until the first put
    store.put(1, _slab(1))
    assert store.capacity_bytes == 2 * _slab(1).nbytes
    store.put(2, _slab(2))
    store.put(3, _slab(3))  # evicts hash 1 — historical 2-block semantics
    assert len(store.store) == 2 and 1 not in store


def test_host_store_demote_hook_fires_outside_eviction():
    demoted = []
    one = _slab(0)
    store = HostKVStore(capacity_blocks=0, block_size=4,
                        capacity_bytes=2 * one.nbytes)
    store.demote_hook = lambda h, s: demoted.append(h)
    for i in range(4):
        store.put(i, _slab(i))
    assert demoted == [0, 1]
    assert store.demotions == 2


def test_probe_extension_is_non_mutating():
    store = HostKVStore(capacity_blocks=8, block_size=4)
    toks = list(range(16))
    for h, s in zip(chain_hashes(toks, 4), [_slab(i) for i in range(4)]):
        store.put(h, s)
    order_before = list(store.store)
    q, h = store.queries, store.hits
    # 17 tokens → 4 full blocks usable, all resident
    assert store.probe_extension(toks + [99], start_block=0) == 4
    assert store.probe_extension([7] * 17, start_block=0) == 0
    assert (store.queries, store.hits) == (q, h)
    assert list(store.store) == order_before
    # match_extension IS a cache use: counters and LRU move
    store.match_extension(toks + [99], start_block=0)
    assert store.hits == 4 and store.queries == 4


# ---------------------------------------------------------------------------
# engine: async prefetch pipeline
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        # HBM pool deliberately tiny (14 blocks) so contexts are evicted
        # between uses; the host tier is byte-sized (the new knob)
        cache=CacheConfig(block_size=4, num_blocks=14,
                          kv_host_cache_bytes=1 << 22,
                          kv_prefetch_workers=1),
        scheduler=SchedulerConfig(max_num_seqs=2, max_num_batched_tokens=64),
        mesh=MeshConfig(data=1, tensor=1),
    )
    mesh = build_mesh(cfg.mesh)
    params = init_or_load(cfg.model, mesh, seed=0)
    return cfg, mesh, params


def _churn(eng, n=3, length=24):
    for i in range(n):
        other = list(np.random.default_rng(100 + i).integers(1, 500, length))
        eng.generate([other], GREEDY)


def test_async_prefetch_roundtrip_bit_identical(setup):
    cfg, mesh, params = setup
    eng = LLMEngine(cfg, mesh=mesh, params=params, num_blocks=14)
    assert eng._prefetcher is not None
    prompt = list(np.random.default_rng(5).integers(1, 500, 24))

    first = eng.generate([prompt], GREEDY)["offline-0"]
    _churn(eng)  # evict the prompt's blocks from the 14-block pool

    again = eng.generate([prompt], GREEDY)["offline-0"]
    assert again == first
    assert eng._prefetcher.committed > 0, "warm tier never prefetched"
    assert eng.prefetch_blocks > 0
    assert eng.host_kv.hits > 0

    snap = eng.tier_stats()
    assert snap["tiers"]["host"]["hits"] == eng.host_kv.hits
    assert snap["tiers"]["host"]["bytes_used"] == eng.host_kv.used_bytes
    assert snap["bytes"].get("host_in", 0) > 0   # promoted toward HBM
    assert snap["bytes"].get("host_out", 0) > 0  # offloaded/demoted down
    assert 0.0 <= snap["prefetch"]["overlap_fraction"] <= 1.0
    assert snap["prefetch"]["count"] == eng.prefetch_count
    assert eng.stats()["kv_tier"] is snap or eng.stats()["kv_tier"] == snap


def test_abort_mid_prefetch_leaks_nothing(setup):
    cfg, mesh, params = setup
    eng = LLMEngine(cfg, mesh=mesh, params=params, num_blocks=14)
    prompt = list(np.random.default_rng(6).integers(1, 500, 24))
    eng.generate([prompt], GREEDY)
    _churn(eng)

    # slow the host lookup so the job is guaranteed in flight at abort
    orig = eng.host_kv.match_extension

    def slow_match(tokens, start_block):
        time.sleep(0.3)
        return orig(tokens, start_block)

    eng.host_kv.match_extension = slow_match
    free_before = eng.scheduler.allocator.num_free_blocks
    eng.add_request("park-me", prompt_token_ids=list(prompt), sampling=GREEDY)
    eng.step()  # admission parks the sequence in PREFETCHING
    seq = eng.scheduler.seqs.get("park-me")
    assert seq is not None and seq.status is SequenceStatus.PREFETCHING
    dropped_before = eng._prefetcher.dropped

    assert eng.abort_request("park-me")
    eng.host_kv.match_extension = orig
    # let the in-flight job land, then poll: the commit-time recheck must
    # discard the staged slabs (the blocks may already be someone else's)
    eng._prefetcher.wait_any(5.0)
    eng._poll_prefetches()
    assert eng._prefetcher.dropped == dropped_before + 1
    assert eng.scheduler.allocator.num_free_blocks == free_before
    assert not eng.has_unfinished()
    # the pool is fully serviceable afterwards
    out = eng.generate([prompt], GREEDY)["offline-0"]
    assert len(out) == GREEDY.max_tokens


# ---------------------------------------------------------------------------
# tier chaos drills (remote tier via ChaosKVServer)
# ---------------------------------------------------------------------------

def start_chaos_kv(**kw):
    srv = ChaosKVServer(**kw)
    holder = {}

    def serve():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        loop.run_until_complete(srv.start())
        holder["url"] = srv.url
        holder["loop"] = loop
        loop.run_forever()

    threading.Thread(target=serve, daemon=True).start()
    for _ in range(200):
        if "url" in holder:
            break
        time.sleep(0.02)
    assert "url" in holder, "chaos kv server failed to start"
    return srv, holder


def _remote_engine(mesh, params, cfg_model, url):
    cfg = EngineConfig(
        model=cfg_model,
        cache=CacheConfig(block_size=4, num_blocks=14, remote_kv_url=url),
        scheduler=SchedulerConfig(max_num_seqs=2, max_num_batched_tokens=64),
        mesh=MeshConfig(data=1, tensor=1),
    )
    return LLMEngine(cfg, mesh=mesh, params=params, num_blocks=14)


def test_corrupt_remote_fetch_is_a_clean_miss(setup):
    """docs/kv_tiering.md failure matrix: a corrupt/short remote block must
    re-prefill (clean miss), never import garbage — greedy output stays
    bit-identical to the cold run in every mode."""
    cfg, mesh, params = setup
    srv, holder = start_chaos_kv(capacity_blocks=256)
    try:
        eng = _remote_engine(mesh, params, cfg.model, holder["url"])
        prompt = list(np.random.default_rng(7).integers(1, 500, 24))
        first = eng.generate([prompt], GREEDY)["offline-0"]
        for _ in range(100):  # puts are async fire-and-forget
            if srv.server.puts >= 5:
                break
            time.sleep(0.05)
        assert srv.server.puts >= 5

        for mode in ("corrupt", "truncate", "down"):
            srv.set_mode(mode)
            _churn(eng)
            q_before = eng.remote_kv.queries
            again = eng.generate([prompt], GREEDY)["offline-0"]
            assert again == first, f"mode {mode} corrupted the output"
            if mode != "down":
                assert eng.remote_kv.queries > q_before

        # healed: the same prompt now genuinely imports from the remote tier
        srv.set_mode(None)
        _churn(eng)
        committed_before = eng._prefetcher.committed
        hits_before = eng.remote_kv.hits
        again = eng.generate([prompt], GREEDY)["offline-0"]
        assert again == first
        assert eng.remote_kv.hits > hits_before
        assert eng._prefetcher.committed > committed_before
    finally:
        holder["loop"].call_soon_threadsafe(holder["loop"].stop)


def test_engine_death_mid_prefetch_leaves_fleet_clean(setup):
    """docs/kv_tiering.md failure matrix: an engine dying mid-prefetch
    leaves nothing to clean fleet-side — stores are content-addressed and
    idempotent, and a replacement engine serves identically."""
    cfg, mesh, params = setup
    srv, holder = start_chaos_kv(capacity_blocks=256)
    try:
        eng_a = _remote_engine(mesh, params, cfg.model, holder["url"])
        prompt = list(np.random.default_rng(8).integers(1, 500, 24))
        first = eng_a.generate([prompt], GREEDY)["offline-0"]
        for _ in range(100):
            if srv.server.puts >= 5:
                break
            time.sleep(0.05)
        blocks_before = len(srv.server.blocks)
        del eng_a

        # engine B dies (is dropped) with a prefetch in flight
        eng_b = _remote_engine(mesh, params, cfg.model, holder["url"])
        orig = eng_b._prefetcher._lookup
        eng_b._prefetcher._lookup = (
            lambda toks, start: (time.sleep(0.5), orig(toks, start))[1])
        eng_b.add_request("doomed", prompt_token_ids=list(prompt),
                          sampling=GREEDY)
        eng_b.step()
        assert eng_b.scheduler.seqs["doomed"].status is (
            SequenceStatus.PREFETCHING)
        del eng_b

        # the remote tier is unharmed and a fresh engine reuses it
        assert len(srv.server.blocks) >= blocks_before
        eng_c = _remote_engine(mesh, params, cfg.model, holder["url"])
        again = eng_c.generate([prompt], GREEDY)["offline-0"]
        assert again == first
        assert eng_c.remote_kv.hits >= 5
    finally:
        holder["loop"].call_soon_threadsafe(holder["loop"].stop)


def test_chaos_kv_mode_validation():
    srv = ChaosKVServer()
    with pytest.raises(ValueError):
        srv.set_mode("bogus")
    for m in (None, "corrupt", "truncate", "hang", "down"):
        srv.set_mode(m)


# ---------------------------------------------------------------------------
# kv_server hardening
# ---------------------------------------------------------------------------

def test_kv_server_oversized_put_413():
    from aiohttp.test_utils import TestClient, TestServer

    async def main():
        server = KVServer(capacity_blocks=8, max_block_bytes=64)
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            r = await client.put("/blocks/big", data=b"x" * 100)
            assert r.status == 413
            body = await r.json()
            assert body["limit"] == 64
            assert server.rejected == 1
            r = await client.put("/blocks/ok", data=b"y" * 10)
            assert r.status == 200
            stats = await (await client.get("/stats")).json()
            assert stats["rejected"] == 1 and stats["puts"] == 1
            assert stats["bytes"] == 10
        finally:
            await client.close()

    asyncio.run(main())


def test_kv_server_ttl_sweep_and_stats():
    from aiohttp.test_utils import TestClient, TestServer

    async def main():
        server = KVServer(capacity_blocks=8, ttl_seconds=30.0)
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            await client.put("/blocks/a", data=b"aa",
                             headers={"X-KV-Meta": '{"k":1}'})
            await client.put("/blocks/b", data=b"bb")
            # a GET refreshes b's idle clock; a stays stale
            now = time.time()
            server.blocks["a"] = (server.blocks["a"][0],
                                  server.blocks["a"][1], now - 60.0)
            assert server.sweep_expired(now=now) == 1
            assert "a" not in server.blocks and "b" in server.blocks
            assert server.expired == 1 and server.used_bytes == 2
            r = await client.get("/blocks/a")
            assert r.status == 404
            stats = await (await client.get("/stats")).json()
            assert stats["expired"] == 1 and stats["misses"] == 1
            metrics = await (await client.get("/metrics")).text()
            for name in ("kvserver:bytes", "kvserver:expired_total",
                         "kvserver:rejected_total",
                         "kvserver:evictions_total"):
                assert name in metrics
        finally:
            await client.close()

    asyncio.run(main())


def test_kv_server_ttl_disabled_never_expires():
    server = KVServer(capacity_blocks=8, ttl_seconds=0.0)
    server.blocks["a"] = (b"aa", "{}", 0.0)
    assert server.sweep_expired(now=1e12) == 0
    assert "a" in server.blocks


def test_kv_server_reput_updates_bytes_without_double_count():
    async def main():
        from aiohttp.test_utils import TestClient, TestServer

        server = KVServer(capacity_blocks=8)
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            await client.put("/blocks/a", data=b"x" * 10)
            await client.put("/blocks/a", data=b"y" * 4)
            assert server.used_bytes == 4
            assert server.puts == 1  # re-put refreshes, not a new block
        finally:
            await client.close()

    asyncio.run(main())


# ---------------------------------------------------------------------------
# router: expected-cached-prefix scoring
# ---------------------------------------------------------------------------

def test_tier_import_weight():
    assert tier_import_weight(10.0, 5.0) == pytest.approx(0.5)
    assert tier_import_weight(0.0, 5.0) == 0.0
    assert tier_import_weight(5.0, 10.0) == 0.0  # import slower: worthless
    assert tier_import_weight(1e9, 5.0) == pytest.approx(1.0, abs=1e-6)


def test_tier_factor_cascade():
    f = PrefixAwareRouter._tier_factor
    assert f(None) == 1.0
    assert f(EngineStats()) == 1.0  # no ratios scraped → boolean degenerate
    assert f(EngineStats(kv_tier_hit_ratio={"hbm": 1.0})) == 1.0
    assert f(EngineStats(kv_tier_hit_ratio={"host": 1.0})) == pytest.approx(
        TIER_WEIGHTS["host"])
    # warm tiers only matter for the share HBM already missed
    assert f(EngineStats(kv_tier_hit_ratio={"hbm": 0.5, "host": 1.0})
             ) == pytest.approx(0.5 + 0.7 * 0.5)
    assert f(EngineStats(kv_tier_hit_ratio={"hbm": 0.5, "host": 0.5,
                                            "remote": 1.0})
             ) == pytest.approx(0.5 + 0.7 * 0.25 + 0.35 * 0.25)
    # out-of-range scraped values are clamped
    assert f(EngineStats(kv_tier_hit_ratio={"hbm": 7.0})) == 1.0


def test_hashtrie_endpoint_match_lengths():
    trie = HashTrie(chunk_size=4)
    trie.insert("aaaabbbbcccc", "deep")
    trie.insert("aaaabbbb", "mid")
    trie.insert("aaaa", "shallow")
    depths = trie.endpoint_match_lengths("aaaabbbbcccc",
                                         {"deep", "mid", "shallow"})
    assert depths == {"deep": 12, "mid": 8, "shallow": 4}
    # availability filters the walk
    assert trie.endpoint_match_lengths("aaaabbbbcccc", {"mid"}) == {"mid": 8}
    assert trie.endpoint_match_lengths("zzzz", {"deep"}) == {}


def test_score_endpoints_hotter_shallower_beats_colder_deeper():
    router = PrefixAwareRouter(prefix_min_match_length=0, chunk_size=4,
                               use_native_trie=False)
    router.trie.insert("aaaabbbbcccc", "cold")  # depth 12
    router.trie.insert("aaaa", "hot")           # depth 4
    stats = {
        "cold": EngineStats(kv_tier_hit_ratio={"hbm": 0.05}),
        "hot": EngineStats(kv_tier_hit_ratio={"hbm": 0.9}),
    }
    scores = router.score_endpoints(
        "aaaabbbbcccc", {"cold", "hot"}, {"cold"}, 12, stats)
    assert scores["hot"] > scores["cold"]
    # stats-less endpoints keep the boolean deepest-match behaviour
    scores = router.score_endpoints(
        "aaaabbbbcccc", {"cold", "hot"}, {"cold"}, 12, {})
    assert scores["cold"] == 12 and scores["hot"] == 4


def test_prefix_router_tier_routing_end_to_end():
    from production_stack_tpu.router.protocols import EndpointInfo

    router = PrefixAwareRouter(prefix_min_match_length=0, chunk_size=4,
                               use_native_trie=False)
    eps = [EndpointInfo(url="http://cold"), EndpointInfo(url="http://hot")]
    router.trie.insert("aaaabbbbcccc", "http://cold")
    router.trie.insert("aaaabbbb", "http://hot")
    stats = {
        "http://cold": EngineStats(kv_tier_hit_ratio={"hbm": 0.05}),
        "http://hot": EngineStats(kv_tier_hit_ratio={"hbm": 0.8,
                                                     "host": 0.9}),
    }
    url = asyncio.run(router.route_request(
        eps, stats, {}, {},
        {"prompt": "aaaabbbbcccc", "model": "m"}))
    # 8 * (0.8 + 0.7*0.9*0.2) = 7.4 beats 12 * 0.05 = 0.6
    assert url == "http://hot"


def test_engine_stats_parses_tier_family():
    text = "\n".join([
        'vllm:kv_tier_hit_ratio{model_name="m",tier="hbm"} 0.75',
        'vllm:kv_tier_hit_ratio{model_name="m",tier="host"} 0.5',
        'vllm:kv_tier_hit_ratio{model_name="m",tier="remote"} 0.25',
        "vllm:kv_prefetch_overlap_fraction 0.93",
        "vllm:num_requests_running 2",
    ])
    stats = EngineStats.from_scrape(text)
    assert stats.kv_tier_hit_ratio == {"hbm": 0.75, "host": 0.5,
                                       "remote": 0.25}
    assert stats.kv_prefetch_overlap_fraction == pytest.approx(0.93)
    assert stats.num_running_requests == 2


# ---------------------------------------------------------------------------
# stacktop HOSTHIT column
# ---------------------------------------------------------------------------

def test_stacktop_host_hit_column():
    from tools.stacktop import COLUMNS, _fmt_host_hit, engine_row_cells

    row = {"kv_tier": {"tiers": {"host": {"hits": 3, "queries": 4}}}}
    assert _fmt_host_hit(row) == "75.0%"
    assert _fmt_host_hit({}) == "-"  # engines without tiering
    assert _fmt_host_hit({"kv_tier": {"tiers": {"host": {"queries": 0}}}}
                         ) == "-"
    cells = engine_row_cells({"url": "http://e", "kv_tier": row["kv_tier"]})
    assert len(cells) == len(COLUMNS)
    assert "75.0%" in cells
