"""Multi-LoRA batching: adapters live in a device bank and a single batch
mixes base + different adapters, each token selecting its own low-rank
path. Verified against solo runs."""

import asyncio
import json
import os
import tempfile

import numpy as np
import pytest
from safetensors.numpy import save_file

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.server import EngineServer
from production_stack_tpu.parallel.mesh import MeshConfig


def make_adapter_dir(cfg: ModelConfig, seed: int, rank: int = 4,
                     scale: float = 8.0) -> str:
    """HF-PEFT-shaped adapter touching q/v/down of layer 0 and q of layer 1."""
    d = tempfile.mkdtemp()
    rng = np.random.default_rng(seed)
    E, H, KH, D, F = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                      cfg.head_dim, cfg.intermediate_size)

    def ab(in_dim, out_dim):
        return (rng.standard_normal((rank, in_dim)).astype(np.float32) * 0.3,
                rng.standard_normal((out_dim, rank)).astype(np.float32) * 0.3)

    tensors = {}
    for layer, module, in_dim, out_dim in (
        (0, "self_attn.q_proj", E, H * D),
        (0, "self_attn.v_proj", E, KH * D),
        (0, "mlp.down_proj", F, E),
        (1, "self_attn.q_proj", E, H * D),
    ):
        A, B = ab(in_dim, out_dim)
        base = f"base_model.model.model.layers.{layer}.{module}"
        tensors[f"{base}.lora_A.weight"] = A
        tensors[f"{base}.lora_B.weight"] = B
    save_file(tensors, os.path.join(d, "adapter_model.safetensors"))
    with open(os.path.join(d, "adapter_config.json"), "w") as f:
        json.dump({"r": rank, "lora_alpha": scale}, f)
    return d


def make_server() -> EngineServer:
    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=256,
                          enable_prefix_caching=False),
        scheduler=SchedulerConfig(max_num_seqs=4,
                                  max_num_batched_tokens=64),
        mesh=MeshConfig(data=1, tensor=1),
    )
    return EngineServer(cfg)


REQ = {"prompt": "hello lora", "max_tokens": 6, "temperature": 0,
       "ignore_eos": True}


async def completion(client, model):
    r = await client.post("/v1/completions", json=dict(REQ, model=model))
    assert r.status == 200, await r.text()
    return (await r.json())["choices"][0]["text"]


def test_multi_lora_mixed_batch():
    async def main():
        from aiohttp.test_utils import TestClient, TestServer

        server = make_server()
        cfg = server.config.model
        dir_a = make_adapter_dir(cfg, seed=1)
        dir_b = make_adapter_dir(cfg, seed=2)
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            base_solo = await completion(client, "tiny-llama")

            for name, d in (("adapter-a", dir_a), ("adapter-b", dir_b)):
                r = await client.post(
                    "/v1/load_lora_adapter",
                    json={"lora_name": name, "lora_path": d},
                )
                assert r.status == 200, await r.text()

            r = await client.get("/v1/models")
            cards = {m["id"]: m for m in (await r.json())["data"]}
            assert cards["adapter-a"]["parent"] == "tiny-llama"
            assert cards["adapter-b"]["parent"] == "tiny-llama"

            # solo runs per model
            a_solo = await completion(client, "adapter-a")
            b_solo = await completion(client, "adapter-b")
            base_with_loaded = await completion(client, "tiny-llama")
            assert base_with_loaded == base_solo  # base weights untouched
            assert a_solo != base_solo
            assert b_solo != a_solo

            # MIXED batch: all three concurrently must reproduce solo outputs
            results = await asyncio.gather(
                completion(client, "tiny-llama"),
                completion(client, "adapter-a"),
                completion(client, "adapter-b"),
            )
            assert results == [base_solo, a_solo, b_solo]

            # unload frees the slot; base unchanged, adapter 404s
            r = await client.post("/v1/unload_lora_adapter",
                                  json={"lora_name": "adapter-a"})
            assert r.status == 200
            assert await completion(client, "tiny-llama") == base_solo
            r = await client.post("/v1/unload_lora_adapter",
                                  json={"lora_name": "adapter-a"})
            assert r.status == 404
        finally:
            await client.close()

    asyncio.run(main())
