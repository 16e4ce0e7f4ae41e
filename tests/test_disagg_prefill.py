"""Disaggregated prefill→decode e2e: a prefill-labeled engine computes the
prompt, its KV blocks move over HTTP to the decode engine, and the decode
engine's allocator prefix-hits the imported context (recomputing only the
final prompt token). Single client call through the orchestrated router
(reference flow: request.py:719-921 with NIXL replaced by block export)."""

import asyncio

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.server import EngineServer
from production_stack_tpu.parallel.mesh import MeshConfig
from production_stack_tpu.router.app import RouterApp, build_parser


def engine_server(role: str = "unified") -> EngineServer:
    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=256),
        scheduler=SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=64),
        mesh=MeshConfig(data=1, tensor=1),
        role=role,
    )
    return EngineServer(cfg)


def test_orchestrated_disagg_with_kv_transfer():
    async def main():
        from aiohttp.test_utils import TestClient, TestServer

        prefill_es, decode_es = engine_server(), engine_server()
        pts, dts = TestServer(prefill_es.build_app()), TestServer(decode_es.build_app())
        await pts.start_server()
        await dts.start_server()
        purl = f"http://127.0.0.1:{pts.port}"
        durl = f"http://127.0.0.1:{dts.port}"

        args = build_parser().parse_args([
            "--service-discovery", "static",
            "--static-backends", f"{purl},{durl}",
            "--static-models", "tiny-llama,tiny-llama",
            "--static-model-labels", "prefill,decode",
            "--routing-logic", "disaggregated_prefill_orchestrated",
        ])
        router = RouterApp(args)
        client = TestClient(TestServer(router.build_app()))
        await client.start_server()
        try:
            prompt = "a shared forty-plus token prompt for the disaggregated "
            prompt += "prefill path to move across engines"
            r = await client.post(
                "/v1/completions",
                json={"model": "tiny-llama", "prompt": prompt, "max_tokens": 4,
                      "temperature": 0, "ignore_eos": True},
            )
            assert r.status == 200, await r.text()
            body = await r.json()
            assert body["usage"]["completion_tokens"] == 4

            # prefill engine computed the prompt; decode engine prefix-hit
            # the transferred blocks (cached > 0) instead of recomputing
            p_stats = prefill_es.engine.stats()
            d_stats = decode_es.engine.stats()
            assert p_stats["prompt_tokens_total"] > 0
            assert d_stats["gpu_prefix_cache_hits_total"] > 0, d_stats
            assert body["usage"]["prompt_tokens_details"]["cached_tokens"] > 0

            # result must equal a colocated run of the same request
            solo_es = engine_server()
            sts = TestServer(solo_es.build_app())
            await sts.start_server()
            import aiohttp

            async with aiohttp.ClientSession() as s:
                async with s.post(
                    f"http://127.0.0.1:{sts.port}/v1/completions",
                    json={"model": "tiny-llama", "prompt": prompt,
                          "max_tokens": 4, "temperature": 0,
                          "ignore_eos": True},
                ) as solo:
                    solo_body = await solo.json()
            assert body["choices"][0]["text"] == solo_body["choices"][0]["text"]
            await sts.close()
        finally:
            await client.close()
            await pts.close()
            await dts.close()

    asyncio.run(main())


def test_streamed_disagg_pushed_handoff_bit_identical():
    """The streamed two-hop path over REAL engines with --role pools:
    the prefill engine runs the prompt to first token and pushes its
    paged KV into the decode engine's /kv/recv; the decode engine
    splices the transfer decode-ready (no re-prefill) and streams the
    remainder. The client's assembled stream and usage are bit-identical
    / token-exact against a unified single-engine run."""

    async def main():
        import json

        import aiohttp
        from aiohttp.test_utils import TestClient, TestServer

        prefill_es = engine_server(role="prefill")
        decode_es = engine_server(role="decode")
        pts = TestServer(prefill_es.build_app())
        dts = TestServer(decode_es.build_app())
        await pts.start_server()
        await dts.start_server()
        purl = f"http://127.0.0.1:{pts.port}"
        durl = f"http://127.0.0.1:{dts.port}"

        args = build_parser().parse_args([
            "--service-discovery", "static",
            "--static-backends", f"{purl},{durl}",
            "--static-models", "tiny-llama,tiny-llama",
            "--static-backend-roles", "prefill,decode",
            "--routing-logic", "disaggregated_prefill_orchestrated",
        ])
        router = RouterApp(args)
        client = TestClient(TestServer(router.build_app()))
        await client.start_server()
        try:
            prompt = "a shared forty-plus token prompt for the streamed "
            prompt += "disaggregated handoff to move across engines"
            payload = {"model": "tiny-llama", "prompt": prompt,
                       "max_tokens": 6, "temperature": 0,
                       "ignore_eos": True, "stream": True}
            buf = b""
            async with client.post("/v1/completions", json=payload) as r:
                assert r.status == 200, await r.text()
                async for chunk in r.content.iter_any():
                    buf += chunk
            events, done = [], False
            for block in buf.split(b"\n\n"):
                if not block.startswith(b"data: "):
                    continue
                data = block[len(b"data: "):]
                if data == b"[DONE]":
                    done = True
                else:
                    events.append(json.loads(data))
            assert done
            text = "".join(e["choices"][0]["text"]
                           for e in events if e.get("choices"))
            usage = events[-1]["usage"]

            # the wire handoff really ran: prefill pushed, decode
            # received, and nothing stayed parked (the splice consumed it)
            assert prefill_es.metrics.transfer_totals.get(
                "push", {}).get("count", 0) >= 1, \
                prefill_es.metrics.transfer_totals
            assert decode_es.metrics.transfer_totals.get(
                "recv", {}).get("count", 0) >= 1, \
                decode_es.metrics.transfer_totals
            assert not decode_es._kv_transfers
            # the decode engine spliced the transfer decode-ready: it
            # never re-prefilled the continuation prompt
            d_stats = decode_es.engine.stats()
            assert d_stats["spliced_seqs_total"] == 1, d_stats
            assert prefill_es.engine.stats()["spliced_seqs_total"] == 0

            # unified reference run of the same request
            solo_es = engine_server()
            sts = TestServer(solo_es.build_app())
            await sts.start_server()
            ref = dict(payload, stream=False)
            async with aiohttp.ClientSession() as s:
                async with s.post(f"http://127.0.0.1:{sts.port}"
                                  "/v1/completions", json=ref) as solo:
                    solo_body = await solo.json()
            await sts.close()
            assert text == solo_body["choices"][0]["text"]
            assert usage["completion_tokens"] == \
                solo_body["usage"]["completion_tokens"] == 6
            assert usage["prompt_tokens"] == \
                solo_body["usage"]["prompt_tokens"]
            assert usage["total_tokens"] == solo_body["usage"]["total_tokens"]
        finally:
            await client.close()
            await pts.close()
            await dts.close()

    asyncio.run(main())
