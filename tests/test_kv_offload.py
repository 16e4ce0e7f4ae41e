"""Host-DRAM KV offload tier: blocks evicted from HBM survive in the host
store and are re-imported instead of recomputed, with identical outputs."""

import dataclasses

import numpy as np
import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.kv_offload import HostKVStore
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.weights import init_or_load
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

GREEDY = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)


def test_host_store_chain_semantics():
    store = HostKVStore(capacity_blocks=4, block_size=4)
    toks = list(range(16))
    slabs = np.arange(4 * 2 * 4 * 4 * 8, dtype=np.float32).reshape(4, 2, 4, 4, 8)
    assert store.put_sequence(toks, slabs) == 4
    got, n = store.match_extension(toks + [99], start_block=0)
    assert n == 4
    np.testing.assert_array_equal(got[2], slabs[2])
    # different tokens → different chain → miss
    _, n = store.match_extension([7] * 17, start_block=0)
    assert n == 0
    # capacity LRU: adding a new chain evicts the oldest slabs
    store.put_sequence(list(range(100, 116)), slabs)
    assert len(store.store) == 4


@pytest.fixture(scope="module")
def setup():
    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        # HBM pool deliberately tiny (14 blocks) so finished contexts are
        # evicted; host tier holds 64 blocks
        cache=CacheConfig(block_size=4, num_blocks=14, host_offload_blocks=64),
        scheduler=SchedulerConfig(max_num_seqs=2, max_num_batched_tokens=64),
        mesh=MeshConfig(data=1, tensor=1),
    )
    mesh = build_mesh(cfg.mesh)
    params = init_or_load(cfg.model, mesh, seed=0)
    return cfg, mesh, params


def test_offload_roundtrip_after_eviction(setup):
    cfg, mesh, params = setup
    eng = LLMEngine(cfg, mesh=mesh, params=params, num_blocks=14)
    prompt = list(np.random.default_rng(5).integers(1, 500, 24))

    first = eng.generate([prompt], GREEDY)["offline-0"]
    assert eng.host_kv.stores > 0  # finished context copied to host tier

    # churn the tiny HBM pool so the first prompt's blocks are evicted
    for i in range(3):
        other = list(np.random.default_rng(100 + i).integers(1, 500, 24))
        eng.generate([other], GREEDY)

    hits_before = eng.host_kv.hits
    again = eng.generate([prompt], GREEDY)["offline-0"]
    assert again == first  # identical output from re-imported KV
    assert eng.host_kv.hits > hits_before, "host tier was never hit"
    s = eng.stats()
    assert s["cpu_prefix_cache_hits_total"] == eng.host_kv.hits
    assert 0 < s["cpu_cache_usage_perc"] <= 1


def test_offload_disabled_by_default(setup):
    cfg, mesh, params = setup
    cfg2 = dataclasses.replace(cfg, cache=CacheConfig(block_size=4, num_blocks=64))
    eng = LLMEngine(cfg2, mesh=mesh, params=params, num_blocks=64)
    assert eng.host_kv is None
    eng.generate([[1, 2, 3, 4, 5]], GREEDY)
    assert eng.stats()["cpu_cache_usage_perc"] == 0.0
