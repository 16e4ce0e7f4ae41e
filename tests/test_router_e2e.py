"""End-to-end slice: router ↔ two real (tiny) TPU-stack engines on CPU.

This is the reference's routing e2e tier (tests/e2e/test-routing.py) shrunk
to process-local aiohttp test servers — full data path: OpenAI request →
router (discovery, routing, stats, failover) → engine (scheduler, paged
attention) → SSE stream back.
"""

import asyncio
import json
import time

import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.server import EngineServer
from production_stack_tpu.parallel.mesh import MeshConfig
from production_stack_tpu.router.app import RouterApp, build_parser


def engine_server() -> EngineServer:
    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=512),
        scheduler=SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=64),
        mesh=MeshConfig(data=1, tensor=1),
    )
    return EngineServer(cfg)


async def spawn_engines(n):
    from aiohttp.test_utils import TestServer

    servers, urls = [], []
    for _ in range(n):
        es = engine_server()
        ts = TestServer(es.build_app())
        await ts.start_server()
        servers.append((es, ts))
        urls.append(f"http://127.0.0.1:{ts.port}")
    return servers, urls


async def router_client(urls, extra_args=()):
    from aiohttp.test_utils import TestClient, TestServer

    args = build_parser().parse_args(
        [
            "--service-discovery", "static",
            "--static-backends", ",".join(urls),
            "--static-models", ",".join(["tiny-llama"] * len(urls)),
            *extra_args,
        ]
    )
    router = RouterApp(args)
    client = TestClient(TestServer(router.build_app()))
    await client.start_server()
    return router, client


async def teardown(servers, client):
    await client.close()
    for _, ts in servers:
        await ts.close()


def test_models_and_completion_through_router():
    async def main():
        servers, urls = await spawn_engines(2)
        router, client = await router_client(urls)
        try:
            r = await client.get("/v1/models")
            data = await r.json()
            assert [m["id"] for m in data["data"]] == ["tiny-llama"]

            r = await client.post(
                "/v1/completions",
                json={"model": "tiny-llama", "prompt": "hello", "max_tokens": 4,
                      "temperature": 0, "ignore_eos": True},
            )
            assert r.status == 200
            body = await r.json()
            assert body["usage"]["completion_tokens"] == 4
            assert "x-request-id" in r.headers

            r = await client.get("/health")
            assert r.status == 200
        finally:
            await teardown(servers, client)

    asyncio.run(main())


def test_roundrobin_spreads_load():
    async def main():
        servers, urls = await spawn_engines(2)
        router, client = await router_client(urls)
        try:
            for i in range(4):
                r = await client.post(
                    "/v1/completions",
                    json={"model": "tiny-llama", "prompt": f"req {i}",
                          "max_tokens": 2, "temperature": 0, "ignore_eos": True},
                )
                assert r.status == 200
            counts = [s.engine.total_output_tokens for s, _ in servers]
            assert all(c > 0 for c in counts), f"uneven: {counts}"
        finally:
            await teardown(servers, client)

    asyncio.run(main())


def test_session_stickiness_e2e():
    async def main():
        servers, urls = await spawn_engines(2)
        router, client = await router_client(
            urls, ("--routing-logic", "session", "--session-key", "x-user-id")
        )
        try:
            for _ in range(4):
                r = await client.post(
                    "/v1/completions",
                    json={"model": "tiny-llama", "prompt": "hi", "max_tokens": 2,
                          "temperature": 0, "ignore_eos": True},
                    headers={"x-user-id": "alice"},
                )
                assert r.status == 200
            counts = [s.engine.total_output_tokens for s, _ in servers]
            assert sorted(counts) == [0, 8], f"not sticky: {counts}"
        finally:
            await teardown(servers, client)

    asyncio.run(main())


def test_failover_reroutes_around_dead_backend():
    async def main():
        servers, urls = await spawn_engines(1)
        dead = "http://127.0.0.1:1"  # nothing listens here
        router, client = await router_client(
            [dead, urls[0]],
            ("--max-instance-failover-reroute-attempts", "2"),
        )
        try:
            ok = 0
            for i in range(4):
                r = await client.post(
                    "/v1/completions",
                    json={"model": "tiny-llama", "prompt": f"r{i}", "max_tokens": 2,
                          "temperature": 0, "ignore_eos": True},
                )
                ok += r.status == 200
            assert ok == 4
        finally:
            await teardown(servers, client)

    asyncio.run(main())


def test_streaming_and_metrics_through_router():
    async def main():
        servers, urls = await spawn_engines(1)
        router, client = await router_client(urls)
        try:
            r = await client.post(
                "/v1/chat/completions",
                json={"model": "tiny-llama",
                      "messages": [{"role": "user", "content": "hi"}],
                      "max_tokens": 3, "temperature": 0, "stream": True,
                      "ignore_eos": True},
            )
            assert r.status == 200
            lines = [l async for l in r.content]
            text = b"".join(lines).decode()
            assert "data: [DONE]" in text
            # the router injects include_usage for its own token accounting
            # and must strip the usage-only chunk the client didn't ask for
            assert '"usage"' not in text or '"choices": []' not in text
            import json as _json

            for line in text.splitlines():
                if line.startswith("data: ") and line != "data: [DONE]":
                    assert _json.loads(line[6:]).get("choices") != []
            # ...while the router-side token counters got populated
            from production_stack_tpu.router import metrics as rm

            vals = [
                s.value
                for metric in rm.output_tokens_total.collect()
                for s in metric.samples
            ]
            assert sum(vals) >= 3

            # scrape engines once, then router /metrics must expose the
            # dashboard gauge set
            from production_stack_tpu.router.stats import get_engine_stats_scraper

            await get_engine_stats_scraper().scrape_once()
            r = await client.get("/metrics")
            body = await r.text()
            for name in ("vllm:num_requests_running", "vllm:current_qps",
                         "vllm:healthy_pods_total", "vllm:request_latency_seconds",
                         "vllm:gpu_cache_usage_perc"):
                assert name in body, f"missing {name}"
        finally:
            await teardown(servers, client)

    asyncio.run(main())


def test_api_key_auth():
    async def main():
        import tempfile

        servers, urls = await spawn_engines(1)
        keyfile = tempfile.NamedTemporaryFile("w", suffix=".keys", delete=False)
        keyfile.write("sk-valid-key\n")
        keyfile.close()
        router, client = await router_client(
            urls, ("--api-key-file", keyfile.name)
        )
        try:
            body = {"model": "tiny-llama", "prompt": "x", "max_tokens": 2,
                    "temperature": 0, "ignore_eos": True}
            r = await client.post("/v1/completions", json=body)
            assert r.status == 401
            r = await client.post(
                "/v1/completions", json=body,
                headers={"Authorization": "Bearer wrong"},
            )
            assert r.status == 401
            r = await client.post(
                "/v1/completions", json=body,
                headers={"Authorization": "Bearer sk-valid-key"},
            )
            assert r.status == 200
            # control-plane endpoints are guarded too (a keyless /sleep
            # would be a fleet-wide DoS)
            r = await client.post("/sleep")
            assert r.status == 401
            r = await client.get("/v1/models")
            assert r.status == 401
            # health/metrics stay open for probes and scraping
            r = await client.get("/health")
            assert r.status == 200
            r = await client.get("/metrics")
            assert r.status == 200
        finally:
            await teardown(servers, client)

    asyncio.run(main())


def test_unknown_model_404_vs_503():
    async def main():
        servers, urls = await spawn_engines(1)
        router, client = await router_client(urls)
        try:
            r = await client.post(
                "/v1/completions", json={"model": "nope", "prompt": "x"}
            )
            assert r.status == 404
        finally:
            await teardown(servers, client)

    asyncio.run(main())


def test_unsupported_modality_clean_501_and_responses_proxied():
    """Engines advertise capabilities in /v1/models; the router must refuse
    audio/images with a clean 501 up front (VERDICT r3 #5) while proxying
    /v1/responses — which the engine now serves natively — through fine."""
    async def main():
        servers, urls = await spawn_engines(1)
        router, client = await router_client(urls, extra_args=(
            "--static-query-models",
            "--static-backend-health-checks",
            "--health-check-interval", "0.2",
        ))
        try:
            # wait for the first /v1/models probe to land capabilities
            from production_stack_tpu.router.service_discovery import (
                get_service_discovery,
            )
            for _ in range(50):
                eps = get_service_discovery().get_endpoint_info()
                if eps and eps[0].capabilities is not None:
                    break
                await asyncio.sleep(0.1)
            assert eps and "responses" in eps[0].capabilities

            r = await client.post("/v1/audio/speech", json={
                "model": "tiny-llama", "input": "hello", "voice": "x"})
            assert r.status == 501, await r.text()
            body = await r.json()
            assert body["error"]["code"] == "unsupported_endpoint"
            assert "audio.speech" in body["error"]["message"]

            r = await client.post("/v1/images/generations", json={
                "model": "tiny-llama", "prompt": "a cat"})
            assert r.status == 501

            r = await client.post("/v1/responses", json={
                "model": "tiny-llama", "input": "through the router",
                "max_output_tokens": 4, "temperature": 0,
                "ignore_eos": True})
            assert r.status == 200, await r.text()
            body = await r.json()
            assert body["object"] == "response"
            assert body["usage"]["output_tokens"] == 4
        finally:
            await teardown(servers, client)

    asyncio.run(main())


def test_no_capability_advertisement_means_no_filtering():
    """Backends that don't advertise capabilities (external vLLM/whisper
    pods) must keep today's proxy-through behavior: the request reaches
    the backend instead of being 501'd."""
    async def main():
        from aiohttp.test_utils import TestServer

        from production_stack_tpu.testing.fake_engine import FakeEngine

        fe = FakeEngine(model="tiny-llama")  # capabilities=None
        ts = TestServer(fe.build_app())
        await ts.start_server()
        router, client = await router_client(
            [f"http://127.0.0.1:{ts.port}"],
            extra_args=("--static-query-models",
                        "--static-backend-health-checks",
                        "--health-check-interval", "0.2"),
        )
        try:
            await asyncio.sleep(0.5)
            # the fake engine has no /v1/audio route: the router must still
            # forward (404/405 from the backend, NOT a router-side 501)
            r = await client.post("/v1/audio/speech", json={
                "model": "tiny-llama", "input": "hi", "voice": "x"})
            assert r.status != 501
        finally:
            await client.close()
            await ts.close()

    asyncio.run(main())


def test_static_model_types_enable_capability_filtering():
    """--static-model-types (the reference's flag, its whisper tutorial
    passes `transcription`) declares an EXTERNAL backend's modality so
    filtering works without a capability card: chat against a declared
    transcription backend 501s; a declared chat backend proxies."""
    async def main():
        from aiohttp.test_utils import TestServer

        from production_stack_tpu.testing.fake_engine import FakeEngine

        fe = FakeEngine(model="whisper-ext")  # capabilities=None
        ts = TestServer(fe.build_app())
        await ts.start_server()
        router, client = await router_client(
            [f"http://127.0.0.1:{ts.port}"],
            extra_args=("--static-model-types", "transcription"),
        )
        # router_client hardcodes tiny-llama as the model name; the
        # declared TYPE is per-backend so the filter still applies
        try:
            r = await client.post("/v1/chat/completions", json={
                "model": "tiny-llama",
                "messages": [{"role": "user", "content": "hi"}]})
            assert r.status == 501, await r.text()
            body = await r.json()
            assert body["error"]["code"] == "unsupported_endpoint"
        finally:
            await client.close()
            await ts.close()

        # bad type is rejected at startup
        import pytest

        from production_stack_tpu.router.service_discovery import (
            StaticServiceDiscovery,
        )

        with pytest.raises(ValueError, match="unsupported static model"):
            StaticServiceDiscovery(["http://x"], ["m"],
                                   model_types=["banana"])

    asyncio.run(main())


def test_static_model_types_length_mismatch_fails_at_startup():
    import pytest

    from production_stack_tpu.router.service_discovery import (
        StaticServiceDiscovery,
    )

    with pytest.raises(ValueError, match="entries for"):
        StaticServiceDiscovery(["http://a", "http://b", "http://c"],
                               ["m"] * 3,
                               model_types=["chat", "transcription"])


# -- request-lifecycle observability (docs/observability.md) -----------------

def test_x_request_id_echoed_on_every_router_response():
    async def main():
        servers, urls = await spawn_engines(1)
        router, client = await router_client(urls)
        try:
            # success path, client-supplied id echoed
            r = await client.post(
                "/v1/completions",
                json={"model": "tiny-llama", "prompt": "hi", "max_tokens": 2,
                      "temperature": 0, "ignore_eos": True},
                headers={"x-request-id": "my-id-1"},
            )
            assert r.status == 200
            assert r.headers["x-request-id"] == "my-id-1"

            # error paths carry one too (generated when absent)
            r = await client.post("/v1/completions", data=b"{not json",
                                  headers={"Content-Type": "application/json"})
            assert r.status == 400
            assert r.headers["x-request-id"]

            r = await client.post(
                "/v1/completions",
                json={"model": "no-such-model", "prompt": "x"},
                headers={"x-request-id": "my-id-2"},
            )
            assert r.status == 404
            assert r.headers["x-request-id"] == "my-id-2"

            # non-proxy surfaces are covered by the middleware as well
            r = await client.get("/health")
            assert r.headers["x-request-id"]
        finally:
            await teardown(servers, client)

    asyncio.run(main())


def test_request_lifecycle_observability_acceptance(monkeypatch):
    """ISSUE acceptance: one trace across router and engine with per-stage
    timing, non-empty per-stage histograms, and a /debug/requests timeline
    carrying the propagated x-request-id.

    The image ships only the opentelemetry API (NoOp tracer), so span
    recording is faked: one shared RecordingTracer is patched into BOTH
    tracing modules; parenting is tracked with a contextvar and trace ids
    come from the explicitly extracted W3C context (raw traceparent
    forwarding is what carries the id between tiers, as in production
    API-only mode)."""
    import contextlib
    import contextvars

    from opentelemetry import trace as ot

    from production_stack_tpu.engine import tracing as etracing
    from production_stack_tpu.router.experimental import tracing as rtracing

    recorded = []
    current = contextvars.ContextVar("fake_span", default=None)

    class FakeSpan:
        def __init__(self, name, kind, attributes, trace_id, parent):
            self.name = name
            # request_span passes an otel SpanKind enum
            self.kind = getattr(kind, "name", str(kind)).lower()
            self.attributes = dict(attributes or {})
            self.events = []
            self.trace_id = trace_id
            self.parent = parent

        def set_attribute(self, key, value):
            self.attributes[key] = value

        def add_event(self, name, attributes=None):
            self.events.append(name)

    class RecordingTracer:
        @contextlib.contextmanager
        def start_as_current_span(self, name, context=None, kind=None,
                                  attributes=None, **kw):
            parent = current.get()
            if context is not None:
                ctx = ot.get_current_span(context).get_span_context()
                trace_id = (format(ctx.trace_id, "032x")
                            if ctx.trace_id else None)
            else:
                trace_id = parent.trace_id if parent else None
            span = FakeSpan(name, kind, attributes, trace_id,
                            parent.name if parent else None)
            recorded.append(span)
            token = current.set(span)
            try:
                yield span
            finally:
                current.reset(token)

    shared = RecordingTracer()
    trace_id = "0af7651916cd43dd8448eb211c80319c"

    async def main():
        import aiohttp

        t_test = time.time()
        servers, urls = await spawn_engines(1)
        router, client = await router_client(urls)
        # patch AFTER both tiers booted: initialize_tracing (called at
        # startup on each tier) rebuilds the module-global _tracer
        monkeypatch.setattr(rtracing, "_tracer", shared)
        monkeypatch.setattr(etracing, "_tracer", shared)
        try:
            r = await client.post(
                "/v1/completions",
                json={"model": "tiny-llama", "prompt": "hello world",
                      "max_tokens": 6, "temperature": 0, "ignore_eos": True},
                headers={"traceparent":
                         f"00-{trace_id}-b7ad6b7169203331-01",
                         "x-request-id": "acc-1"},
            )
            assert r.status == 200
            assert r.headers["x-request-id"] == "acc-1"

            # (a) one trace: router SERVER span with engine child spans
            # carrying queue/prefill/decode stage timing
            by_name = {s.name: s for s in recorded}
            rs = by_name["router /v1/completions"]
            cs = by_name["backend /v1/completions"]
            es = by_name["engine /v1/completions"]
            assert rs.kind == "server" and rs.trace_id == trace_id
            assert cs.kind == "client" and cs.trace_id == trace_id
            assert cs.parent == rs.name  # child via current-context nesting
            assert es.kind == "server" and es.trace_id == trace_id
            assert rs.attributes["http.status_code"] == 200
            assert rs.attributes["request.id"] == "acc-1"
            assert es.attributes["client.request.id"] == "acc-1"
            for key in ("stage.queue_s", "stage.prefill_s", "stage.decode_s"):
                assert es.attributes[key] >= 0.0, es.attributes
            assert "admitted" in es.events and "first_token" in es.events
            # the stages are sums of the record's time-to-first-token parts
            at = es.attributes
            assert at["stage.queue_s"] == pytest.approx(
                at["stage.server_prep_s"] + at["stage.intake_wait_s"]
                + at["stage.queue_wait_s"])
            assert at["stage.prefill_s"] == pytest.approx(
                at["stage.stream_wait_s"] + at["stage.prefill_steps_s"])

            # (b) new per-stage histograms exported and non-empty
            async with aiohttp.ClientSession() as s:
                async with s.get(urls[0] + "/metrics") as mr:
                    text = await mr.text()
            for name in ("vllm:request_queue_time_seconds_count",
                         "vllm:request_prefill_time_seconds_count",
                         "vllm:request_decode_time_seconds_count",
                         "vllm:inter_token_latency_seconds_count",
                         "vllm:scheduler_step_duration_seconds_count"):
                count = sum(
                    float(line.rsplit(" ", 1)[1])
                    for line in text.splitlines() if line.startswith(name))
                assert count > 0, f"{name} empty"

            # (c) /debug/requests: ordered timeline + propagated id,
            # aggregated across both tiers by the router
            r = await client.get("/debug/requests")
            data = await r.json()
            rrec = next(x for x in data["router"]["requests"]
                        if x["request_id"] == "acc-1")
            assert rrec["trace_id"] == trace_id
            assert rrec["outcome"] == "completed" and rrec["status"] == 200
            assert rrec["attempts"][0]["status"] == 200
            assert rrec["attempts"][0]["backend"] == urls[0]
            (engine_view,) = data["engines"].values()
            erec = next(x for x in engine_view["requests"]
                        if x["client_request_id"] == "acc-1")
            assert erec["trace_id"] == trace_id
            tl = erec["timeline"]
            stamps = [tl[k] for k in ("received", "enqueued", "arrival",
                                      "admitted", "first_launch",
                                      "first_token", "last_token",
                                      "finished")]
            assert stamps == sorted(stamps), f"out of order: {tl}"
            # the router's forward instant, beside the handler's own
            assert t_test <= erec["router_sent_unix"] <= erec["received_unix"]
        finally:
            await teardown(servers, client)

    asyncio.run(main())
