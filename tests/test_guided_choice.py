"""guided_choice — sequence-level constrained selection.

vLLM's guided_choice constrains generation to one of N strings via a
token-walk; here the engine scores every choice exactly —
log P(choice | prompt) in one batched teacher-forced dense pass
(``choice_logprobs``) — and the server picks the argmax (temperature 0)
or samples from softmax(logP / T). The output is always exactly one of
the given strings, with whole-sequence probabilities.
"""

import asyncio

import jax
import numpy as np
import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.models import llama
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh


def _engine():
    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=128),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_num_batched_tokens=32,
            ),
        mesh=MeshConfig(data=1, tensor=1),
    )
    mesh = build_mesh(cfg.mesh, devices=jax.devices()[:1])
    return LLMEngine(cfg, mesh=mesh, num_blocks=128)


def _manual_logprob(engine, prompt, cont):
    """Reference: dense forward, sum log-softmax of continuation tokens."""
    cfg = engine.config.model
    import jax.numpy as jnp

    seq = prompt + cont
    toks = jnp.asarray(np.asarray([seq], np.int32))
    logits = np.asarray(
        llama.forward_dense(cfg, engine.runner.params, toks), np.float64
    )[0]
    lp = 0.0
    for j in range(len(prompt), len(seq)):
        row = logits[j - 1]
        row = row - row.max()
        lp += row[seq[j]] - np.log(np.exp(row).sum())
    return lp


def test_choice_logprobs_match_manual():
    engine = _engine()
    prompt = [5, 6, 7, 8]
    choices = [[10, 11], [12], [13, 14, 15]]
    got = engine.choice_logprobs(prompt, choices)
    want = [_manual_logprob(engine, prompt, c) for c in choices]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_choice_logprobs_beyond_top_bucket():
    """prompt+choice longer than the largest prefill bucket (64 here) but
    within max_model_len must score, not crash — the dense pass pads to
    the next power of two past the bucket clamp."""
    engine = _engine()
    prompt = list(np.arange(1, 101) % 500)  # 100 tokens
    choices = [[10, 11], [12]]
    got = engine.choice_logprobs(prompt, choices)
    want = [_manual_logprob(engine, prompt, c) for c in choices]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def _serve(handler_coro):
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.server import EngineServer

    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=128),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_num_batched_tokens=32,
            ),
    )
    server = EngineServer(cfg)

    async def main():
        async with TestClient(TestServer(server.build_app())) as c:
            await handler_coro(c)

    asyncio.run(main())


def test_server_guided_choice_returns_a_choice():
    choices = ["positive", "negative", "neutral"]

    async def drive(c):
        r = await c.post("/v1/completions", json={
            "prompt": "Classify: great product!",
            "guided_choice": choices, "temperature": 0,
        })
        assert r.status == 200
        body = await r.json()
        assert body["choices"][0]["text"] in choices
        assert body["choices"][0]["finish_reason"] == "stop"
        # deterministic at temperature 0
        r2 = await c.post("/v1/completions", json={
            "prompt": "Classify: great product!",
            "guided_choice": choices, "temperature": 0,
        })
        assert (await r2.json())["choices"][0]["text"] == \
            body["choices"][0]["text"]

        # chat + streaming: single content chunk then DONE
        r = await c.post("/v1/chat/completions", json={
            "messages": [{"role": "user", "content": "pick"}],
            "guided_choice": choices, "temperature": 0, "stream": True,
        })
        assert r.status == 200
        raw = (await r.read()).decode()
        assert raw.rstrip().endswith("data: [DONE]")
        import json as j

        first = j.loads(raw.split("data: ")[1].split("\n")[0])
        assert first["choices"][0]["delta"]["content"] in choices

        # sampled selection still returns one of the choices
        r = await c.post("/v1/completions", json={
            "prompt": "Classify:", "guided_choice": choices,
            "temperature": 1.5, "seed": 7,
        })
        assert (await r.json())["choices"][0]["text"] in choices

    _serve(drive)


def test_server_guided_choice_validation():
    async def drive(c):
        for bad in ([], ["ok", ""], "notalist", ["x"] * 65):
            r = await c.post("/v1/completions", json={
                "prompt": "p", "guided_choice": bad,
            })
            assert r.status == 400, bad
        r = await c.post("/v1/completions", json={
            "prompt": "p", "guided_choice": ["a", "b"], "n": 2,
        })
        assert r.status == 400

    _serve(drive)
