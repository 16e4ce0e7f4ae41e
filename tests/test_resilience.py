"""Resilience layer (docs/resilience.md): circuit-breaker state machine,
retry budget, hedged requests, and end-to-end deadline propagation with
engine-side cancellation that frees KV blocks.

The integration tests are the fast deterministic version of the ISSUE's
acceptance drill: one sick backend out of three (error_rate=0.5 +
first-byte stall), the breaker ejects it, every client request still
succeeds, retry amplification stays under the budget cap, and first
attempts stop landing on the sick pod. The long flapping-backend version
lives in test_router_soak.py (opt-in soak tier).
"""

import asyncio
import time

import pytest

from production_stack_tpu.router.resilience import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    CircuitBreaker,
    HedgePolicy,
    ResilienceConfig,
    RetryBudget,
)


def _cfg(**kw) -> ResilienceConfig:
    base = dict(min_samples=4, ewma_alpha=0.5, error_threshold=0.5,
                open_cooldown=10.0, half_open_probes=2,
                latency_factor=3.0, latency_min_samples=3,
                retry_budget_ratio=0.5, retry_budget_min=1,
                retry_budget_window=60.0)
    base.update(kw)
    return ResilienceConfig(**base)


# -- circuit breaker state machine (injected clock, no I/O) -----------------

def test_breaker_opens_on_error_rate_and_filters():
    cb = CircuitBreaker(_cfg())
    t = 1000.0
    sick, ok = "http://sick", "http://ok"
    cb.record_success(ok, now=t)
    for _ in range(4):
        cb.record_failure(sick, now=t)
    assert cb.state(sick) == OPEN
    assert cb.state(ok) == CLOSED
    # an ejected backend receives no first attempts...
    assert cb.filter([sick, ok], now=t) == [ok]
    # ...unless it is the only backend: degraded beats none
    assert cb.filter([sick], now=t) == [sick]


def test_breaker_volume_guard():
    """One unlucky 500 below min_samples must not eject a backend."""
    cb = CircuitBreaker(_cfg())
    cb.record_failure("http://b", now=0.0)
    assert cb.state("http://b") == CLOSED


def test_breaker_half_open_probe_then_close():
    cb = CircuitBreaker(_cfg())
    t = 1000.0
    url = "http://b"
    for _ in range(4):
        cb.record_failure(url, now=t)
    assert cb.state(url) == OPEN
    # cooldown not yet expired: still ejected
    assert cb.filter([url, "http://ok"], now=t + 5) == ["http://ok"]
    # cooldown expired: traffic flips the breaker to half-open
    assert url in cb.filter([url, "http://ok"], now=t + 11)
    assert cb.state(url) == HALF_OPEN
    # probe slots are finite while convalescing
    cb.on_attempt_start(url)
    cb.on_attempt_start(url)
    assert url not in cb.filter([url, "http://ok"], now=t + 11)
    # one good probe closes the circuit
    cb.record_success(url, now=t + 12)
    assert cb.state(url) == CLOSED
    assert url in cb.filter([url, "http://ok"], now=t + 12)


def test_breaker_probe_failure_reopens():
    cb = CircuitBreaker(_cfg())
    t = 1000.0
    url = "http://b"
    for _ in range(4):
        cb.record_failure(url, now=t)
    cb.filter([url], now=t + 11)
    assert cb.state(url) == HALF_OPEN
    cb.record_failure(url, now=t + 11)
    assert cb.state(url) == OPEN
    # the re-trip restarts the cooldown from the probe failure
    assert cb.filter([url, "http://ok"], now=t + 15) == ["http://ok"]


def test_breaker_respects_retry_after():
    """A 429 Retry-After opens immediately (past the volume guard) and
    overrides the default cooldown for that trip."""
    cb = CircuitBreaker(_cfg())
    t = 1000.0
    url = "http://b"
    for _ in range(4):
        cb.record_success(url, now=t)
    cb.record_failure(url, "overload", retry_after=30.0, now=t)
    assert cb.state(url) == OPEN
    # default cooldown (10s) elapsed but Retry-After (30s) has not
    assert cb.filter([url, "http://ok"], now=t + 15) == ["http://ok"]
    assert url in cb.filter([url, "http://ok"], now=t + 31)


def test_breaker_latency_outlier_ejection():
    cb = CircuitBreaker(_cfg())
    t = 1000.0
    slow, a, b = "http://slow", "http://a", "http://b"
    for _ in range(5):
        cb.record_success(a, ttfb=0.02, now=t)
        cb.record_success(b, ttfb=0.02, now=t)
        cb.record_success(slow, ttfb=0.5, now=t)
    assert cb.state(slow) == OPEN
    assert cb.state(a) == CLOSED and cb.state(b) == CLOSED


def test_breaker_disabled_is_passthrough():
    cb = CircuitBreaker(_cfg(breaker_enabled=False))
    for _ in range(10):
        cb.record_failure("http://b", now=0.0)
    assert cb.filter(["http://b"], now=0.0) == ["http://b"]
    assert cb.state("http://b") == CLOSED


# -- retry budget ------------------------------------------------------------

def test_retry_budget_caps_amplification():
    rb = RetryBudget(_cfg())  # min 1, ratio 0.5
    t = 1000.0
    for i in range(4):
        rb.on_request(now=t + i)
    # cap = 1 + 0.5 * 4 = 3
    assert rb.remaining(now=t + 4) == 3
    assert rb.try_acquire(now=t + 4)
    assert rb.try_acquire(now=t + 4)
    assert rb.try_acquire(now=t + 4)
    assert not rb.try_acquire(now=t + 4)  # exhausted: shed the retry
    # the window slides: old retries expire and budget recovers
    assert rb.try_acquire(now=t + 70)


# -- hedge policy ------------------------------------------------------------

def test_hedge_policy_delay():
    assert HedgePolicy(_cfg(hedge_enabled=False)).delay() is None
    fixed = HedgePolicy(_cfg(hedge_enabled=True, hedge_delay_ms=80.0))
    assert fixed.delay() == pytest.approx(0.08)
    derived = HedgePolicy(_cfg(hedge_enabled=True, hedge_delay_ms=0.0))
    assert derived.delay() == 1.0  # cold sample: conservative
    now = time.time()
    for i in range(20):
        derived.observe(0.1 if i else 2.0, now=now)
    assert 0.1 <= derived.delay() <= 2.0  # p95 of the observed window


# -- integration: breaker drill through the real router ---------------------

def _router_client(urls, extra_args=()):
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.router.app import RouterApp, build_parser

    args = build_parser().parse_args([
        "--service-discovery", "static",
        "--static-backends", ",".join(urls),
        "--static-models", ",".join(["fake-model"] * len(urls)),
        "--routing-logic", "roundrobin",
        *extra_args,
    ])
    router = RouterApp(args)
    return TestClient(TestServer(router.build_app()))


def test_breaker_drill_ejects_sick_backend():
    """1 of 3 backends injects error_rate=0.5 + a first-byte stall; the
    breaker must eject it, every client request must still succeed, the
    retry budget must cap amplification, and — once open — first-attempt
    traffic must stop landing on the sick pod entirely."""
    from aiohttp.test_utils import TestServer

    from production_stack_tpu.router.resilience import get_resilience
    from production_stack_tpu.testing.fake_engine import FakeEngine
    from production_stack_tpu.testing.faults import FaultSpec

    async def main():
        engines = [
            FakeEngine(model="fake-model", tokens_per_second=2000, ttft=0.001,
                       faults=FaultSpec.parse("error_rate=0.5,stall_ms=100,"
                                              "seed=7")),
            FakeEngine(model="fake-model", tokens_per_second=2000, ttft=0.001),
            FakeEngine(model="fake-model", tokens_per_second=2000, ttft=0.001),
        ]
        servers = []
        for e in engines:
            ts = TestServer(e.build_app())
            await ts.start_server()
            servers.append(ts)
        urls = [f"http://127.0.0.1:{ts.port}" for ts in servers]
        sick_url, sick = urls[0], engines[0]

        client = _router_client(urls, (
            "--max-instance-failover-reroute-attempts", "3",
            "--cb-min-samples", "4",
            "--cb-ewma-alpha", "0.5",
            "--cb-open-cooldown", "60",   # stays open for the whole test
        ))
        await client.start_server()
        try:
            n_phase1 = 45
            fails = 0
            for i in range(n_phase1):
                r = await client.post(
                    "/v1/completions",
                    json={"model": "fake-model", "prompt": f"drill {i}",
                          "max_tokens": 4})
                fails += r.status != 200
                await r.release()
            assert fails == 0, f"{fails}/{n_phase1} drill requests failed"

            res = get_resilience()
            assert res is not None
            assert res.breaker.state(sick_url) == OPEN, (
                "breaker never ejected the sick backend: "
                f"{res.breaker.states()}")

            # amplification stays under the budget: attempts - requests
            # is the retry count, capped at min + ratio * requests
            attempts = sum(e.total_requests for e in engines)
            cap = 3 + int(0.2 * n_phase1)
            assert attempts - n_phase1 <= cap, (
                f"{attempts - n_phase1} retries exceeds budget cap {cap}")

            # with the circuit open, NO first attempt reaches the sick pod
            seen = sick.total_requests
            for i in range(15):
                r = await client.post(
                    "/v1/completions",
                    json={"model": "fake-model", "prompt": f"post {i}",
                          "max_tokens": 4})
                assert r.status == 200
                await r.release()
            assert sick.total_requests == seen, (
                "ejected backend still receives first attempts")
        finally:
            await client.close()
            for ts in servers:
                await ts.close()

    asyncio.run(main())


def test_hedged_request_wins_on_fast_backend():
    """With hedging on, a slow primary is raced by a delayed hedge on the
    other backend; the hedge wins well before the primary would finish
    and the hedged-requests counter ticks."""
    from aiohttp.test_utils import TestServer

    from production_stack_tpu.router import metrics as rm
    from production_stack_tpu.testing.fake_engine import FakeEngine

    async def main():
        engines = [FakeEngine(model="fake-model", tokens_per_second=2000)
                   for _ in range(2)]
        servers = []
        for e in engines:
            ts = TestServer(e.build_app())
            await ts.start_server()
            servers.append(ts)
        urls = [f"http://127.0.0.1:{ts.port}" for ts in servers]
        # roundrobin sorts by URL, so the primary hits sorted(urls)[0]:
        # make that one the slow backend so the hedge must save the request
        slow_i = urls.index(sorted(urls)[0])
        slow, fast = engines[slow_i], engines[1 - slow_i]
        slow.ttft, fast.ttft = 0.8, 0.005

        client = _router_client(urls, (
            "--enable-hedging", "--hedge-delay-ms", "50",
        ))
        await client.start_server()
        hedged_before = rm.hedged_requests_total._value.get()
        try:
            t0 = time.monotonic()
            r = await client.post(
                "/v1/completions",
                json={"model": "fake-model", "prompt": "hedge me",
                      "max_tokens": 4})
            elapsed = time.monotonic() - t0
            assert r.status == 200
            body = await r.json()
            assert body["choices"][0]["text"]
            assert elapsed < 0.6, (
                f"hedge did not win: {elapsed:.2f}s (primary ttft 0.8s)")
            assert fast.total_requests == 1
            assert slow.total_requests == 1  # primary fired, then lost
            assert rm.hedged_requests_total._value.get() == hedged_before + 1
        finally:
            await client.close()
            for ts in servers:
                await ts.close()

    asyncio.run(main())


# -- integration: deadlines + engine-side cancellation ----------------------

def test_deadline_propagation_and_kv_reclamation():
    """The engine honors x-request-deadline: pre-expired → immediate 504;
    mid-generation expiry → 504 (full) / in-band error (stream), and in
    both cases the sequences leave the scheduler and the KV free-block
    count returns to its pre-request baseline."""
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
    )
    from production_stack_tpu.engine.server import EngineServer

    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=128),
        scheduler=SchedulerConfig(max_num_seqs=2,
                                  max_num_batched_tokens=64),
    )
    server = EngineServer(cfg)

    async def wait_blocks(baseline, timeout=10.0):
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            if server.engine.scheduler.num_free_blocks == baseline:
                return True
            await asyncio.sleep(0.05)
        return False

    async def main():
        async with TestClient(TestServer(server.build_app())) as c:
            # a completed request establishes the steady-state baseline
            r = await c.post("/v1/completions",
                             json={"prompt": "warm", "max_tokens": 2,
                                   "temperature": 0, "ignore_eos": True})
            assert r.status == 200
            baseline = server.engine.scheduler.num_free_blocks
            aborted0 = server.engine.aborted_seqs

            # already-expired deadline: refused before admission
            r = await c.post("/v1/completions",
                             json={"prompt": "late", "max_tokens": 2},
                             headers={"x-request-deadline": "1.0"})
            assert r.status == 504
            assert server.engine.aborted_seqs == aborted0  # never admitted

            # malformed deadline degrades to no deadline, not a 400
            r = await c.post("/v1/completions",
                             json={"prompt": "odd", "max_tokens": 2,
                                   "temperature": 0, "ignore_eos": True},
                             headers={"x-request-deadline": "soon"})
            assert r.status == 200

            # mid-generation expiry (non-streaming): 504, KV reclaimed
            r = await c.post(
                "/v1/completions",
                json={"prompt": "expire me", "max_tokens": 400,
                      "temperature": 0, "ignore_eos": True},
                headers={"x-request-deadline": f"{time.time() + 0.15:.3f}"})
            assert r.status == 504
            body = await r.json()
            assert body["error"]["type"] == "timeout_error"
            assert await wait_blocks(baseline), (
                "KV blocks not reclaimed after deadline abort: "
                f"{server.engine.scheduler.num_free_blocks} != {baseline}")
            assert server.engine.aborted_seqs > aborted0

            # mid-stream expiry: in-band error before [DONE], KV reclaimed
            aborted1 = server.engine.aborted_seqs
            r = await c.post(
                "/v1/completions",
                json={"prompt": "expire stream", "max_tokens": 400,
                      "temperature": 0, "ignore_eos": True, "stream": True},
                headers={"x-request-deadline": f"{time.time() + 0.15:.3f}"})
            assert r.status == 200  # stream already committed
            text = await r.text()
            assert "deadline exceeded" in text
            assert text.rstrip().endswith("data: [DONE]")
            assert await wait_blocks(baseline)
            assert server.engine.aborted_seqs > aborted1

    asyncio.run(main())


def test_client_disconnect_frees_kv_blocks():
    """Dropping the connection mid-stream aborts the sequence: KV blocks
    return to baseline and the aborted-seqs counter ticks."""
    import aiohttp
    from aiohttp.test_utils import TestServer

    from production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
    )
    from production_stack_tpu.engine.server import EngineServer

    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=128),
        scheduler=SchedulerConfig(max_num_seqs=2,
                                  max_num_batched_tokens=64),
    )
    server = EngineServer(cfg)

    async def main():
        ts = TestServer(server.build_app())
        await ts.start_server()
        try:
            async with aiohttp.ClientSession() as s:
                r = await s.post(
                    f"http://127.0.0.1:{ts.port}/v1/completions",
                    json={"prompt": "warm", "max_tokens": 2,
                          "temperature": 0, "ignore_eos": True})
                assert r.status == 200
                await r.read()
            baseline = server.engine.scheduler.num_free_blocks
            aborted0 = server.engine.aborted_seqs

            async with aiohttp.ClientSession() as s:
                r = await s.post(
                    f"http://127.0.0.1:{ts.port}/v1/completions",
                    json={"prompt": "disconnect me", "max_tokens": 400,
                          "temperature": 0, "ignore_eos": True,
                          "stream": True})
                assert r.status == 200
                await r.content.read(64)  # first bytes prove it's running
                r.close()  # hang up mid-stream

            t0 = time.monotonic()
            while time.monotonic() - t0 < 10.0:
                if (server.engine.scheduler.num_free_blocks == baseline
                        and server.engine.aborted_seqs > aborted0):
                    break
                await asyncio.sleep(0.05)
            assert server.engine.scheduler.num_free_blocks == baseline, (
                "disconnect leaked KV blocks: "
                f"{server.engine.scheduler.num_free_blocks} != {baseline}")
            assert server.engine.aborted_seqs > aborted0
        finally:
            await ts.close()

    asyncio.run(main())


def test_queue_full_returns_429_with_retry_after():
    """max_queue_len overflow is an honest overload: 429 + Retry-After
    (which the router breaker respects) instead of unbounded queueing."""
    from production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
    )
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.sampling import SamplingParams
    from production_stack_tpu.engine.scheduler import SchedulerQueueFull

    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=64),
        scheduler=SchedulerConfig(max_num_seqs=1, max_queue_len=2),
    )
    engine = LLMEngine(cfg)
    sp = SamplingParams(max_tokens=4, ignore_eos=True)
    engine.add_request("q0", prompt_token_ids=[1, 2, 3], sampling=sp)
    engine.add_request("q1", prompt_token_ids=[1, 2, 3], sampling=sp)
    with pytest.raises(SchedulerQueueFull):
        engine.add_request("q2", prompt_token_ids=[1, 2, 3], sampling=sp)

    from production_stack_tpu.engine.server import EngineServer

    server = EngineServer(cfg, engine=engine, overload_retry_after=2.5)
    resp = server._overloaded("waiting queue full")
    assert resp.status == 429
    assert resp.headers["Retry-After"] == "2.5"


def test_bench_fault_target_parsing():
    """The bench harness' --fault-injection SPEC[@URL] parser."""
    from benchmarks.multi_round_qa import parse_fault_targets

    targets = parse_fault_targets(
        ["error_rate=0.5,stall_ms=500@http://pod-2:8100/",
         "drop_rate=0.1"],
        "http://router:8001")
    assert targets == [
        ("http://pod-2:8100", "error_rate=0.5,stall_ms=500"),
        ("http://router:8001", "drop_rate=0.1"),
    ]
    with pytest.raises(ValueError):
        parse_fault_targets(["@http://pod-2:8100"], "http://r")
