"""Remote KV tier: engine A's finished context lands on the shared kv_server;
a fresh engine B (separate pool, no host tier) imports it at admission and
produces identical output without recomputing the prefix."""

import asyncio
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
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.weights import init_or_load
from production_stack_tpu.kv_server import KVServer
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

GREEDY = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)


def start_kv_server():
    from aiohttp import web

    server = KVServer(capacity_blocks=256)
    holder = {}

    def serve():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(server.build_app())
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", 0)
        loop.run_until_complete(site.start())
        holder["port"] = runner.addresses[0][1]
        holder["loop"] = loop
        loop.run_forever()

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    for _ in range(100):
        if "port" in holder:
            break
        time.sleep(0.05)
    return server, holder


def make_engine(mesh, params, cfg_model, remote_url):
    cfg = EngineConfig(
        model=cfg_model,
        cache=CacheConfig(block_size=4, num_blocks=128,
                          remote_kv_url=remote_url),
        scheduler=SchedulerConfig(max_num_seqs=2,
                                  max_num_batched_tokens=64),
        mesh=MeshConfig(data=1, tensor=1),
    )
    return LLMEngine(cfg, mesh=mesh, params=params, num_blocks=128)


def test_cross_engine_remote_kv_reuse():
    kv, holder = start_kv_server()
    url = f"http://127.0.0.1:{holder['port']}"

    cfg_model = ModelConfig.from_pretrained("tiny-llama")
    mesh = build_mesh(MeshConfig(data=1, tensor=1))
    params = init_or_load(cfg_model, mesh, seed=0)

    prompt = list(np.random.default_rng(9).integers(1, 500, 24))

    engine_a = make_engine(mesh, params, cfg_model, url)
    first = engine_a.generate([prompt], GREEDY)["offline-0"]

    # async writer: wait for the slabs to land
    for _ in range(100):
        if kv.puts >= 5:
            break
        time.sleep(0.05)
    assert kv.puts >= 5, f"engine A never spilled to remote (puts={kv.puts})"

    engine_b = make_engine(mesh, params, cfg_model, url)
    again = engine_b.generate([prompt], GREEDY)["offline-0"]
    assert again == first
    assert engine_b.remote_kv.hits >= 5, "engine B never hit the remote tier"

    # B prefix-cached the imported blocks locally too
    assert engine_b.scheduler.allocator.prefix_queries > 0
    holder["loop"].call_soon_threadsafe(holder["loop"].stop)
