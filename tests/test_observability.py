"""Request-lifecycle observability: flight recorder, tracing degradation
without opentelemetry, and the engine's /debug/requests + per-stage
metrics over a real (tiny) engine on CPU."""

import asyncio
import sys

import pytest

from production_stack_tpu.engine import tracing as etracing
from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.server import EngineServer
from production_stack_tpu.flight_recorder import FlightRecorder
from production_stack_tpu.parallel.mesh import MeshConfig
from production_stack_tpu.router.experimental import tracing as rtracing


# -- flight recorder unit ----------------------------------------------------

def test_flight_recorder_bounded_and_newest_first():
    fr = FlightRecorder(size=3)
    for i in range(5):
        rec = fr.begin(request_id=f"r{i}")
        fr.stamp(rec, "admitted")
        fr.finish(rec, status=200)
    snap = fr.snapshot()
    assert [r["request_id"] for r in snap] == ["r4", "r3", "r2"]
    assert fr.snapshot(limit=1)[0]["request_id"] == "r4"
    stats = fr.stats()
    assert stats["size"] == 3
    assert stats["recorded"] == 3
    assert stats["total"] == 5
    assert stats["dropped"] == 2


def test_flight_recorder_timeline_ordering_and_visibility():
    fr = FlightRecorder(size=8)
    rec = fr.begin(request_id="a", outcome=None)
    assert fr.snapshot() == []  # in-flight records are not visible yet
    fr.stamp(rec, "admitted")
    fr.stamp(rec, "first_token")
    fr.finish(rec, outcome="completed")
    (got,) = fr.snapshot()
    tl = got["timeline"]
    keys = ["received", "admitted", "first_token", "finished"]
    vals = [tl[k] for k in keys]
    assert vals == sorted(vals)
    assert got["outcome"] == "completed"
    assert got["received_unix"] > 0


# -- tracing degrades to a no-op without opentelemetry ----------------------

def test_tracing_noop_without_opentelemetry():
    with pytest.MonkeyPatch.context() as mp:
        # None in sys.modules makes `import opentelemetry` raise
        # ImportError — exactly the missing-package path, without
        # uninstalling anything
        mp.setitem(sys.modules, "opentelemetry", None)
        for mod in (rtracing, etracing):
            assert mod.initialize_tracing("collector:4317") is False
            assert mod.is_enabled() is False
            assert mod.extract_context({"traceparent": "00-ab-cd-01"}) is None
            headers: dict = {}
            assert mod.inject_headers(headers) == {}
            assert mod.trace_id_hex() is None
            with mod.request_span("x") as span:
                assert span is None
    # restore module state for the rest of the session (the API package
    # IS installed in this image)
    assert rtracing.initialize_tracing(None) is False  # no exporter, but...
    assert rtracing.is_enabled()  # ...propagation is back on
    etracing.initialize_tracing(None)
    assert etracing.is_enabled()


def test_router_and_engine_apps_boot_without_opentelemetry():
    async def main():
        from aiohttp.test_utils import TestClient, TestServer

        from production_stack_tpu.router.app import RouterApp, build_parser

        args = build_parser().parse_args([
            "--service-discovery", "static",
            "--static-backends", "http://127.0.0.1:1",
            "--static-models", "some-model",
            "--otel-endpoint", "collector:4317",
        ])
        router = RouterApp(args)
        client = TestClient(TestServer(router.build_app()))
        await client.start_server()
        try:
            r = await client.get("/health")
            assert r.status == 200
        finally:
            await client.close()

    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(sys.modules, "opentelemetry", None)
        asyncio.run(main())
    rtracing.initialize_tracing(None)
    etracing.initialize_tracing(None)


# -- engine integration: /debug/requests + per-stage metrics ----------------

def make_server() -> EngineServer:
    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=512),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_num_batched_tokens=64,
            ),
        mesh=MeshConfig(data=1, tensor=1),
    )
    return EngineServer(cfg)


@pytest.fixture(scope="module")
def server():
    return make_server()


def _metric_value(text: str, prefix: str) -> float:
    total = 0.0
    for line in text.splitlines():
        if line.startswith(prefix):
            total += float(line.rsplit(" ", 1)[1])
    return total


def test_debug_requests_timeline_and_stage_histograms(server):
    async def fn(client):
        r = await client.post(
            "/v1/completions",
            json={"model": "tiny-llama", "prompt": "hello world",
                  "max_tokens": 6, "temperature": 0, "ignore_eos": True},
            headers={"x-request-id": "obs-req-1"},
        )
        assert r.status == 200
        # propagated id echoed back even when the engine is hit directly
        assert r.headers["x-request-id"] == "obs-req-1"

        r = await client.get("/debug/requests")
        data = await r.json()
        rec = next(x for x in data["requests"]
                   if x["client_request_id"] == "obs-req-1")
        assert rec["endpoint"] == "/v1/completions"
        assert rec["model"] == "tiny-llama"
        assert rec["outcome"] == "completed"
        assert rec["status"] == 200
        assert rec["num_output_tokens"] == 6
        assert rec["num_prompt_tokens"] > 0
        tl = rec["timeline"]
        stages = ["received", "admitted", "first_token", "last_token",
                  "finished"]
        vals = [tl[k] for k in stages]
        assert vals == sorted(vals), f"timeline out of order: {tl}"
        assert data["recorder"]["recorded"] >= 1

        # ?limit caps the returned list
        r = await client.get("/debug/requests?limit=1")
        assert len((await r.json())["requests"]) == 1

        r = await client.get("/metrics")
        text = await r.text()
        for name in (
            "vllm:request_queue_time_seconds_count",
            "vllm:request_prefill_time_seconds_count",
            "vllm:request_decode_time_seconds_count",
            "vllm:inter_token_latency_seconds_count",
            "vllm:scheduler_step_duration_seconds_count",
        ):
            assert _metric_value(text, name) > 0, f"{name} empty:\n{text}"
        assert "vllm:batch_occupancy" in text
        assert _metric_value(text, "vllm:kv_blocks_total") > 0

    asyncio.run(_with_client(server, fn))


def test_streaming_request_recorded(server):
    async def fn(client):
        r = await client.post(
            "/v1/completions",
            json={"model": "tiny-llama", "prompt": "stream me",
                  "max_tokens": 4, "temperature": 0, "ignore_eos": True,
                  "stream": True},
            headers={"x-request-id": "obs-stream-1"},
        )
        assert r.status == 200
        assert r.headers["X-Request-Id"] == "obs-stream-1"
        await r.text()  # drain the SSE stream
        r = await client.get("/debug/requests")
        rec = next(x for x in (await r.json())["requests"]
                   if x["client_request_id"] == "obs-stream-1")
        assert rec["streaming"] is True
        assert rec["outcome"] == "completed"
        assert rec["num_output_tokens"] == 4

    asyncio.run(_with_client(server, fn))


async def _with_client(server, fn):
    from aiohttp.test_utils import TestClient, TestServer

    async with TestClient(TestServer(server.build_app())) as client:
        return await fn(client)
