"""Fault injection (SURVEY §5.3 gap-to-beat — the reference has none):
injected engine faults must be masked by the router's per-request
failover, with health/metrics staying truthful on the sick pod."""

import asyncio
import os

import pytest

from production_stack_tpu.testing.faults import FaultSpec


def test_spec_parsing():
    s = FaultSpec.parse("error_rate=0.3,latency_ms=250,seed=7")
    assert s.error_rate == 0.3 and s.latency_ms == 250 and s.seed == 7
    assert s.active
    assert not FaultSpec.parse("").active
    with pytest.raises(ValueError):
        FaultSpec.parse("explode=1")


def test_flaky_engine_masked_by_failover(monkeypatch):
    """One engine injects 50% errors; every client request still succeeds
    through the router (per-request reroute), and the sick pod's /health
    stays healthy (the hard failure mode: alive but flaky)."""
    from aiohttp.test_utils import TestServer

    from production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
    )
    from production_stack_tpu.engine.server import EngineServer

    def make_server(fault=None):
        cfg = EngineConfig(
            model=ModelConfig.from_pretrained("tiny-llama"),
            cache=CacheConfig(block_size=4, num_blocks=128),
            scheduler=SchedulerConfig(max_num_seqs=2,
                                  max_num_batched_tokens=64),
        )
        if fault:
            monkeypatch.setenv("FAULT_INJECTION", fault)
        else:
            monkeypatch.delenv("FAULT_INJECTION", raising=False)
        return EngineServer(cfg)

    async def main():
        import aiohttp

        sick = make_server("error_rate=0.5,seed=3")
        sick_ts = TestServer(sick.build_app())
        await sick_ts.start_server()
        healthy = make_server(None)
        healthy_ts = TestServer(healthy.build_app())
        await healthy_ts.start_server()

        from production_stack_tpu.router.app import RouterApp, build_parser

        args = build_parser().parse_args([
            "--service-discovery", "static",
            "--static-backends",
            f"http://127.0.0.1:{sick_ts.port},"
            f"http://127.0.0.1:{healthy_ts.port}",
            "--static-models", "tiny-llama,tiny-llama",
            "--routing-logic", "roundrobin",
            "--max-instance-failover-reroute-attempts", "3",
        ])
        from aiohttp.test_utils import TestClient

        router = RouterApp(args)
        async with TestClient(TestServer(router.build_app())) as client:
            fails = 0
            for i in range(10):
                r = await client.post(
                    "/v1/completions",
                    json={"model": "tiny-llama", "prompt": f"req {i}",
                          "max_tokens": 2, "temperature": 0,
                          "ignore_eos": True},
                )
                fails += r.status != 200
            assert fails == 0, f"{fails}/10 requests leaked injected faults"

            # the sick pod still reports healthy (alive-but-flaky)
            async with aiohttp.ClientSession() as s:
                async with s.get(
                    f"http://127.0.0.1:{sick_ts.port}/health"
                ) as hr:
                    assert hr.status == 200
        await sick_ts.close()
        await healthy_ts.close()

    asyncio.run(main())


def test_direct_injected_errors_visible():
    """Without a router in front, the injected 500s surface — proving the
    faults are real, not a no-op."""
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
    )
    from production_stack_tpu.engine.server import EngineServer

    os.environ["FAULT_INJECTION"] = "error_rate=1.0"
    try:
        cfg = EngineConfig(
            model=ModelConfig.from_pretrained("tiny-llama"),
            cache=CacheConfig(block_size=4, num_blocks=64),
            scheduler=SchedulerConfig(max_num_seqs=2,
                                  max_num_batched_tokens=64),
        )
        server = EngineServer(cfg)

        async def main():
            async with TestClient(TestServer(server.build_app())) as c:
                r = await c.post("/v1/completions",
                                 json={"prompt": "x", "max_tokens": 1})
                assert r.status == 500
                body = await r.json()
                assert body["error"]["type"] == "fault_injection"
                r = await c.get("/health")  # never faulted
                assert r.status == 200

        asyncio.run(main())
    finally:
        del os.environ["FAULT_INJECTION"]


def test_live_fault_toggle():
    """With FAULT_INJECTION set (even empty), POST /debug/faults flips
    injection on a running engine with no restart: on → /v1 faults;
    off → healthy again."""
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
    )
    from production_stack_tpu.engine.server import EngineServer

    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=64),
        scheduler=SchedulerConfig(max_num_seqs=2,
                                  max_num_batched_tokens=64),
    )
    os.environ["FAULT_INJECTION"] = ""  # armed, no faults yet
    try:
        server = EngineServer(cfg)

        async def main():
            async with TestClient(TestServer(server.build_app())) as c:
                r = await c.post("/v1/completions",
                                 json={"prompt": "x", "max_tokens": 1})
                assert r.status == 200  # started clean
                r = await c.post("/debug/faults?error_rate=1.0")
                assert (await r.json())["active"]
                r = await c.post("/v1/completions",
                                 json={"prompt": "x", "max_tokens": 1})
                assert r.status == 500
                r = await c.post("/debug/faults?off=0")  # ambiguous → 400
                assert r.status == 400
                r = await c.post("/debug/faults?off=1")
                assert not (await r.json())["active"]
                r = await c.post("/v1/completions",
                                 json={"prompt": "x", "max_tokens": 1})
                assert r.status == 200
                r = await c.post("/debug/faults?error_rate=2.0")  # invalid
                assert r.status == 400

        asyncio.run(main())
    finally:
        del os.environ["FAULT_INJECTION"]


def test_fault_toggle_absent_when_unarmed():
    """An engine started WITHOUT FAULT_INJECTION has no injectable
    surface: /debug/faults does not exist (blast-radius gate)."""
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
    )
    from production_stack_tpu.engine.server import EngineServer

    assert "FAULT_INJECTION" not in os.environ
    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=64),
        scheduler=SchedulerConfig(max_num_seqs=2,
                                  max_num_batched_tokens=64),
    )
    server = EngineServer(cfg)

    async def main():
        async with TestClient(TestServer(server.build_app())) as c:
            r = await c.post("/debug/faults?error_rate=1.0")
            assert r.status == 404

    asyncio.run(main())


def test_latency_and_drop_faults():
    import time

    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
    )
    from production_stack_tpu.engine.server import EngineServer

    def make(spec):
        os.environ["FAULT_INJECTION"] = spec
        cfg = EngineConfig(
            model=ModelConfig.from_pretrained("tiny-llama"),
            cache=CacheConfig(block_size=4, num_blocks=64),
            scheduler=SchedulerConfig(max_num_seqs=2,
                                  max_num_batched_tokens=64),
        )
        return EngineServer(cfg)

    async def main():
        try:
            server = make("latency_ms=300")
            async with TestClient(TestServer(server.build_app())) as c:
                t0 = time.perf_counter()
                r = await c.post("/v1/completions",
                                 json={"prompt": "x", "max_tokens": 1,
                                       "temperature": 0,
                                       "ignore_eos": True})
                assert r.status == 200
                assert time.perf_counter() - t0 >= 0.3

            server = make("drop_rate=1.0")
            async with TestClient(TestServer(server.build_app())) as c:
                import aiohttp

                with pytest.raises((aiohttp.ClientError,
                                    asyncio.TimeoutError,
                                    ConnectionError)):
                    await c.post("/v1/completions",
                                 json={"prompt": "x", "max_tokens": 1})
        finally:
            os.environ.pop("FAULT_INJECTION", None)

    asyncio.run(main())


def test_spec_range_validation():
    with pytest.raises(ValueError):
        FaultSpec.parse("error_rate=0.7,drop_rate=0.5")  # partition > 1
    with pytest.raises(ValueError):
        FaultSpec.parse("error_rate=1.5")
    with pytest.raises(ValueError):
        FaultSpec.parse("latency_ms=-5")
    with pytest.raises(ValueError):
        FaultSpec.parse("stream_abort_rate=1.5")
    with pytest.raises(ValueError):
        FaultSpec.parse("stall_ms=-1")


def test_stall_and_stream_abort_spec_parsing():
    s = FaultSpec.parse("stall_ms=500,stream_abort_rate=0.3,"
                        "stream_abort_after_ms=80")
    assert s.stall_ms == 500 and s.stream_abort_rate == 0.3
    assert s.stream_abort_after_ms == 80
    assert s.active
    assert FaultSpec.parse("stall_ms=10").active
    assert FaultSpec.parse("stream_abort_rate=0.1").active


def test_stall_delays_survivors_only():
    """stall_ms applies AFTER the error roll: a stalled backend looks
    slow-but-correct (the latency-outlier shape), and injected errors
    return without paying the stall."""
    import time

    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.testing.fake_engine import FakeEngine

    async def main():
        fe = FakeEngine(model="fake-model", tokens_per_second=2000,
                        ttft=0.001, faults=FaultSpec.parse("stall_ms=300"))
        async with TestClient(TestServer(fe.build_app())) as c:
            t0 = time.perf_counter()
            r = await c.post("/v1/completions",
                             json={"model": "fake-model", "prompt": "x",
                                   "max_tokens": 2})
            assert r.status == 200
            assert time.perf_counter() - t0 >= 0.3

        fe = FakeEngine(model="fake-model", tokens_per_second=2000,
                        ttft=0.001,
                        faults=FaultSpec.parse("error_rate=1.0,stall_ms=300"))
        async with TestClient(TestServer(fe.build_app())) as c:
            t0 = time.perf_counter()
            r = await c.post("/v1/completions",
                             json={"model": "fake-model", "prompt": "x",
                                   "max_tokens": 2})
            assert r.status == 500
            assert time.perf_counter() - t0 < 0.3  # errors skip the stall

    asyncio.run(main())


def test_stream_abort_truncates_mid_stream():
    """stream_abort_rate kills the transport after real response bytes:
    the client sees a mid-stream truncation, not a clean error."""
    import aiohttp
    from aiohttp.test_utils import TestServer

    from production_stack_tpu.testing.fake_engine import FakeEngine

    async def main():
        fe = FakeEngine(
            model="fake-model", tokens_per_second=20, ttft=0.001,
            faults=FaultSpec.parse(
                "stream_abort_rate=1.0,stream_abort_after_ms=120"))
        ts = TestServer(fe.build_app())
        await ts.start_server()
        try:
            got = b""
            async with aiohttp.ClientSession() as s:
                with pytest.raises((aiohttp.ClientError, ConnectionError,
                                    asyncio.IncompleteReadError)):
                    async with s.post(
                        f"http://127.0.0.1:{ts.port}/v1/completions",
                        json={"model": "fake-model", "prompt": "x",
                              "max_tokens": 32, "stream": True},
                    ) as r:
                        assert r.status == 200
                        async for chunk in r.content.iter_any():
                            got += chunk
            assert b"data: " in got  # real bytes arrived before the cut
            assert b"[DONE]" not in got  # ...but the stream never finished
        finally:
            await ts.close()

    asyncio.run(main())


def test_fake_engine_live_fault_toggle():
    """FakeEngine exposes the same POST /debug/faults live-flip contract
    as the real engine server, so drills drive both identically."""
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.testing.fake_engine import FakeEngine

    async def main():
        fe = FakeEngine(model="fake-model", tokens_per_second=2000,
                        ttft=0.001)
        async with TestClient(TestServer(fe.build_app())) as c:
            r = await c.post("/v1/completions",
                             json={"model": "fake-model", "prompt": "x",
                                   "max_tokens": 2})
            assert r.status == 200  # starts clean
            r = await c.post("/debug/faults?error_rate=1.0")
            assert (await r.json())["active"]
            r = await c.post("/v1/completions",
                             json={"model": "fake-model", "prompt": "x",
                                   "max_tokens": 2})
            assert r.status == 500
            r = await c.post("/debug/faults?off=1")
            assert not (await r.json())["active"]
            r = await c.post("/v1/completions",
                             json={"model": "fake-model", "prompt": "x",
                                   "max_tokens": 2})
            assert r.status == 200
            r = await c.post("/debug/faults?stream_abort_rate=2.0")
            assert r.status == 400

    asyncio.run(main())
