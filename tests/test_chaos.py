"""Chaos drills: the drain / failover / watchdog scenarios from the
resilience design, driven deterministically by testing/chaos.py.

Acceptance drills covered (docs/resilience.md "Drain & migration"):
  a. SIGTERM (drain) mid-stream: in-flight streams run to completion,
     new work sees zero 5xx (failover masks the drain 503 until the
     readiness probe marks the pod draining), the process exits once
     drained, KV blocks are freed.
  b. kill mid-decode: the client still receives the FULL completion —
     resume-from-prefix replay splices the survivor's continuation into
     the original stream, bit-identical to an uninterrupted greedy run.
  c. injected hang: the stuck-step watchdog flips readiness to 503 and
     the router ejects the pod within one probe interval while /health
     stays 200.
"""

import asyncio
import json
import time

import aiohttp
import pytest
from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.testing.chaos import (
    ChaosEvent,
    ChaosFleet,
    ChaosScenario,
)


def _router_client(urls, extra_args=()):
    from production_stack_tpu.router.app import RouterApp, build_parser

    args = build_parser().parse_args([
        "--service-discovery", "static",
        "--static-backends", ",".join(urls),
        "--static-models", ",".join(["fake-model"] * len(urls)),
        "--routing-logic", "roundrobin",
        "--max-instance-failover-reroute-attempts", "3",
        *extra_args,
    ])
    router = RouterApp(args)
    return TestClient(TestServer(router.build_app()))


async def _collect_stream(client, path, payload, timeout=30.0):
    """POST a streaming request and return (status, events, saw_done):
    every ``data:`` JSON event in order, parsed."""
    async def _go():
        buf = b""
        async with client.post(path, json=payload) as r:
            status = r.status
            if status != 200:
                return status, [], False
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
        return status, events, done

    return await asyncio.wait_for(_go(), timeout)


def _text_of(events, chat=False):
    if chat:
        return "".join(
            (e["choices"][0]["delta"] or {}).get("content") or ""
            for e in events if "choices" in e
        )
    return "".join(e["choices"][0]["text"] for e in events if "choices" in e)


def _tokens(n, first=0):
    return "".join(f"tok{i} " for i in range(first, first + n))


# -- harness unit coverage ---------------------------------------------------

def test_chaos_event_validation():
    with pytest.raises(ValueError):
        ChaosEvent(0.0, "explode", 0)
    with pytest.raises(ValueError):
        ChaosEvent(0.0, "fault", 0)  # fault needs a spec string
    ev = ChaosEvent(0.1, "kill", 1)
    assert ev.at == 0.1 and ev.target == 1


def test_fleet_partition_and_heal():
    """kill/partition refuses new connects; heal re-opens the same port."""

    async def main():
        fleet = ChaosFleet(2, tokens_per_second=2000, ttft=0.001)
        urls = await fleet.start()
        payload = {"model": "fake-model", "prompt": "x", "max_tokens": 2}
        try:
            log = await ChaosScenario(
                fleet, [ChaosEvent(0.0, "partition", 0)]).run()
            assert len(log) == 1
            async with aiohttp.ClientSession() as s:
                with pytest.raises(aiohttp.ClientError):
                    await s.post(f"{urls[0]}/v1/completions", json=payload)
                async with s.post(f"{urls[1]}/v1/completions",
                                  json=payload) as r:
                    assert r.status == 200  # the rest of the fleet is fine
                await fleet.heal(0)
                async with s.post(f"{urls[0]}/v1/completions",
                                  json=payload) as r:
                    assert r.status == 200  # same URL works again
        finally:
            await fleet.stop()

    asyncio.run(main())


def test_step_watchdog_detector():
    """The detector logic on a synthetic clock: stall only when the step
    counter is frozen WHILE work is queued; idle and paused are healthy."""
    from production_stack_tpu.engine.lifecycle import StepWatchdog

    class _Eng:
        unfinished = True

        def has_unfinished(self):
            return self.unfinished

    class _AE:
        step_count = 0
        paused = False
        engine = _Eng()

    ae = _AE()
    wd = StepWatchdog(ae, stall_seconds=5.0)
    assert wd.enabled
    assert not wd.check(0.0)   # first look establishes the baseline
    assert not wd.check(4.0)   # within the window
    assert wd.check(6.0)       # frozen >5s with work queued → stalled
    assert wd.stalls_total == 1
    assert wd.progress_age(6.0) == 6.0
    ae.step_count = 1
    assert not wd.check(7.0)   # progress → recovery, readiness restored
    ae.engine.unfinished = False
    assert not wd.check(100.0)  # idle engine is healthy, never stalls
    ae.engine.unfinished = True
    ae.paused = True
    assert not wd.check(200.0)  # sleep mode is deliberate, not a stall
    assert StepWatchdog(ae, stall_seconds=0.0).enabled is False


# -- drill (a): drain mid-stream --------------------------------------------

def test_drain_drill_inflight_completes_zero_5xx():
    """Drain the primary while it streams: the in-flight stream finishes
    intact, and every post-drain request succeeds (the drain 503 is
    masked by per-request failover)."""

    async def main():
        fleet = ChaosFleet(2, tokens_per_second=50, ttft=0.001)
        urls = await fleet.start()
        primary = sorted(urls)[0]  # roundrobin serves sorted()[0] first
        p_idx = fleet.urls.index(primary)
        try:
            async with _router_client(urls) as client:
                chaos = asyncio.ensure_future(ChaosScenario(
                    fleet, [ChaosEvent(0.15, "drain", p_idx)]).run())
                status, events, done = await _collect_stream(
                    client, "/v1/completions",
                    {"model": "fake-model", "prompt": "drill",
                     "max_tokens": 25, "stream": True})
                await chaos
                assert status == 200 and done
                assert _text_of(events) == _tokens(25)
                assert fleet.engines[p_idx].draining
                for i in range(6):  # zero 5xx after the drain started
                    r = await client.post(
                        "/v1/completions",
                        json={"model": "fake-model", "prompt": f"post {i}",
                              "max_tokens": 2})
                    assert r.status == 200, await r.text()
                # the drained engine really did refuse work (then the
                # breaker stopped offering it any)
                assert fleet.engines[p_idx].drain_rejected >= 1
        finally:
            await fleet.stop()

    asyncio.run(main())


def test_drain_under_load_soak():
    """Drain the primary under 200 concurrent streams: zero
    client-visible failures, zero stuck in-flight work afterwards."""

    async def main():
        fleet = ChaosFleet(2, tokens_per_second=400, ttft=0.001)
        urls = await fleet.start()
        p_idx = fleet.urls.index(sorted(urls)[0])
        tokens = 8
        try:
            async with _router_client(urls, (
                "--static-backend-health-checks",
                "--health-check-interval", "0.1",
                # the drain→probe transition window may fail many
                # attempts over at once; the drill measures drain
                # semantics, not budget tuning
                "--retry-budget-min", "300",
            )) as client:

                async def one(i):
                    status, events, done = await _collect_stream(
                        client, "/v1/completions",
                        {"model": "fake-model", "prompt": f"s{i}",
                         "max_tokens": tokens, "stream": True})
                    return (status == 200 and done
                            and _text_of(events) == _tokens(tokens))

                chaos = asyncio.ensure_future(ChaosScenario(
                    fleet, [ChaosEvent(0.05, "drain", p_idx)]).run())
                results = await asyncio.gather(*(one(i)
                                                 for i in range(200)))
                await chaos
                bad = results.count(False)
                assert bad == 0, f"{bad}/200 client-visible failures"
                assert fleet.engines[p_idx].draining
                assert all(e.running == 0 for e in fleet.engines)
        finally:
            await fleet.stop()

    asyncio.run(main())


# -- drill (b): kill mid-decode, resume bit-identical ------------------------

def test_kill_middecode_resume_bit_identical():
    """Kill the serving backend mid-decode: the client's stream continues
    on a survivor via resume-from-prefix replay and the assembled text,
    usage, and stream id are identical to an uninterrupted greedy run."""
    from production_stack_tpu.router import metrics as rm

    n = 30
    payload = {"model": "fake-model", "prompt": "The hedgehog",
               "max_tokens": n, "stream": True, "temperature": 0}

    async def main():
        # reference: uninterrupted run through the same router path
        ref_fleet = ChaosFleet(1, tokens_per_second=500, ttft=0.001)
        ref_urls = await ref_fleet.start()
        try:
            async with _router_client(ref_urls) as client:
                _, ref_events, ref_done = await _collect_stream(
                    client, "/v1/completions", payload)
        finally:
            await ref_fleet.stop()
        assert ref_done
        ref_text = _text_of(ref_events)
        ref_usage = ref_events[-1]["usage"]

        before = rm.stream_resumes_total.labels(
            outcome="resumed")._value.get()
        fleet = ChaosFleet(2, tokens_per_second=40, ttft=0.001)
        urls = await fleet.start()
        p_idx = fleet.urls.index(sorted(urls)[0])
        try:
            async with _router_client(urls) as client:
                chaos = asyncio.ensure_future(ChaosScenario(
                    fleet, [ChaosEvent(0.25, "kill", p_idx)]).run())
                status, events, done = await _collect_stream(
                    client, "/v1/completions", payload)
                await chaos
        finally:
            await fleet.stop()
        assert status == 200 and done
        assert _text_of(events) == ref_text == _tokens(n)
        assert events[-1]["usage"] == ref_usage
        # the splice is invisible: one stream id from start to finish
        assert len({e["id"] for e in events}) == 1
        after = rm.stream_resumes_total.labels(
            outcome="resumed")._value.get()
        assert after == before + 1

    asyncio.run(main())


def test_kill_middecode_resume_multitoken_events():
    """Resume accounting must be token-exact, not event-count-based: with
    several tokens per SSE event (fused steps / holdback flushes), an
    event-count decrement would hand the continuation too large a budget
    and the spliced completion would overrun the client's max_tokens."""
    n = 30
    payload = {"model": "fake-model", "prompt": "The hedgehog",
               "max_tokens": n, "stream": True, "temperature": 0}

    async def main():
        fleet = ChaosFleet(2, tokens_per_second=40, ttft=0.001,
                           tokens_per_chunk=3)
        urls = await fleet.start()
        p_idx = fleet.urls.index(sorted(urls)[0])
        try:
            async with _router_client(urls) as client:
                chaos = asyncio.ensure_future(ChaosScenario(
                    fleet, [ChaosEvent(0.25, "kill", p_idx)]).run())
                status, events, done = await _collect_stream(
                    client, "/v1/completions", payload)
                await chaos
        finally:
            await fleet.stop()
        assert status == 200 and done
        # token-exact budget: exactly max_tokens tokens, never more
        assert _text_of(events) == _tokens(n)
        assert events[-1]["usage"] == {"prompt_tokens": 8,
                                       "completion_tokens": n,
                                       "total_tokens": 8 + n}
        # the router-injected continuous per-chunk usage never leaks to
        # the client: only the final chunk carries usage
        assert all("usage" not in e for e in events[:-1])
        assert len({e["id"] for e in events}) == 1

    asyncio.run(main())


def test_resume_accounting_is_token_based():
    """_ResumeState unit coverage: the max_tokens decrement and the usage
    rewrite both come from the backend's per-chunk usage (tokens), not
    from the relayed SSE event count."""
    from production_stack_tpu.router.request_service import (
        _continuation_body,
        _ResumeState,
    )

    def ev(text, completion_tokens):
        return b"data: " + json.dumps(
            {"id": "s1", "created": 7,
             "choices": [{"index": 0, "text": text,
                          "finish_reason": None}],
             "usage": {"prompt_tokens": 4,
                       "completion_tokens": completion_tokens,
                       "total_tokens": 4 + completion_tokens}}).encode()

    st = _ResumeState(chat=False)
    st.observe(ev("a b c ", 3))  # one SSE event carrying three tokens
    st.observe(ev("d e ", 5))
    assert st.chunks == 2
    assert st.completion_tokens() == 5
    body = _continuation_body({"prompt": "p: ", "max_tokens": 10}, st)
    assert body["prompt"] == "p: a b c d e "
    assert body["max_tokens"] == 5  # 10 - 5 tokens, NOT 10 - 2 events

    st.start_attempt()
    # a backend that ignores continuous_usage_stats: the event count is
    # the accounting floor for the new attempt
    st.observe(b"data: " + json.dumps(
        {"id": "s2", "choices": [{"index": 0, "text": "f ",
                                  "finish_reason": None}]}).encode())
    assert st.completion_tokens() == 6
    # the continuation's final usage covers only its own tokens; the
    # rewrite folds the dead attempts' prefix back in
    out = st.rewrite(b"data: " + json.dumps(
        {"id": "s2", "created": 9, "choices": [],
         "usage": {"prompt_tokens": 9, "completion_tokens": 5,
                   "total_tokens": 14}}).encode())
    data = json.loads(out[len(b"data: "):])
    assert data["id"] == "s1" and data["created"] == 7
    assert data["usage"]["completion_tokens"] == 10
    assert data["usage"]["total_tokens"] == 19


def test_stream_splice_event_helpers():
    """The splice-hygiene helpers: role-only deltas are recognized (and
    only those), and the injected per-chunk usage is stripped from
    content chunks but kept on final chunks."""
    from production_stack_tpu.router.request_service import (
        _is_role_only_event,
        _strip_inline_usage,
    )

    role = (b'data: {"id": "x", "choices": [{"index": 0, '
            b'"delta": {"role": "assistant"}, "finish_reason": null}]}')
    assert _is_role_only_event(role)
    content = (b'data: {"id": "x", "choices": [{"index": 0, "delta": '
               b'{"role": "assistant", "content": "hi"}, '
               b'"finish_reason": null}]}')
    assert not _is_role_only_event(content)
    finish = (b'data: {"id": "x", "choices": [{"index": 0, '
              b'"delta": {"role": "assistant"}, "finish_reason": "stop"}]}')
    assert not _is_role_only_event(finish)

    mid = (b'data: {"choices": [{"index": 0, "text": "t", '
           b'"finish_reason": null}], "usage": {"completion_tokens": 2}}')
    assert b'"usage"' not in _strip_inline_usage(mid)
    final = (b'data: {"choices": [{"index": 0, "text": "", '
             b'"finish_reason": "stop"}], "usage": {"completion_tokens": 2}}')
    assert _strip_inline_usage(final) == final
    usage_only = (b'data: {"choices": [], '
                  b'"usage": {"completion_tokens": 2}}')
    assert _strip_inline_usage(usage_only) == usage_only


def test_all_draining_falls_back_to_full_list(monkeypatch):
    """docs/resilience.md: routing skips draining endpoints, 'falling
    back to the full list only if every endpoint is draining' — a
    single-replica rollout routes to the draining pod (honest 503 +
    Retry-After) instead of refusing outright."""
    import dataclasses

    from production_stack_tpu.router import request_service as rs
    from production_stack_tpu.router.protocols import EndpointInfo
    from production_stack_tpu.router.request_service import RequestService

    eps = [EndpointInfo(url=f"http://e{i}", model_names=["m"],
                        draining=True) for i in range(2)]

    class _Disc:
        def get_endpoint_info(self):
            return eps

    monkeypatch.setattr(rs, "get_service_discovery", lambda: _Disc())
    svc = RequestService.__new__(RequestService)
    assert svc._filter_endpoints("m") == eps  # all draining → full list
    eps[0] = dataclasses.replace(eps[0], draining=False)
    assert svc._filter_endpoints("m") == [eps[0]]  # one healthy → only it


def test_kill_middecode_resume_chat_stream():
    """Same replay drill over /v1/chat/completions: the continuation is
    dispatched as an assistant-prefix message and spliced seamlessly."""
    from production_stack_tpu.router import metrics as rm

    n = 20
    payload = {"model": "fake-model",
               "messages": [{"role": "user", "content": "hi"}],
               "max_tokens": n, "stream": True, "temperature": 0}

    async def main():
        before = rm.stream_resumes_total.labels(
            outcome="resumed")._value.get()
        fleet = ChaosFleet(2, tokens_per_second=40, ttft=0.001)
        urls = await fleet.start()
        p_idx = fleet.urls.index(sorted(urls)[0])
        try:
            async with _router_client(urls) as client:
                chaos = asyncio.ensure_future(ChaosScenario(
                    fleet, [ChaosEvent(0.2, "kill", p_idx)]).run())
                status, events, done = await _collect_stream(
                    client, "/v1/chat/completions", payload)
                await chaos
        finally:
            await fleet.stop()
        assert status == 200 and done
        assert _text_of(events, chat=True) == _tokens(n)
        assert len({e["id"] for e in events}) == 1
        # the continuation opens its own stream with a fresh role delta;
        # the splice must suppress it — the client sees exactly ONE
        # assistant role marker, at the true start of the stream
        roles = [i for i, e in enumerate(events)
                 if any("role" in (c.get("delta") or {})
                        for c in e.get("choices", []))]
        assert roles == [0]
        after = rm.stream_resumes_total.labels(
            outcome="resumed")._value.get()
        assert after == before + 1

    asyncio.run(main())


def test_kill_without_survivor_fails_in_band():
    """No survivor to resume on: the client gets an explicit in-band
    error event + [DONE] instead of a silent truncation."""
    from production_stack_tpu.router import metrics as rm

    async def main():
        before = rm.stream_resumes_total.labels(
            outcome="failed")._value.get()
        fleet = ChaosFleet(1, tokens_per_second=30, ttft=0.001)
        urls = await fleet.start()
        try:
            async with _router_client(
                urls, ("--max-instance-failover-reroute-attempts", "2"),
            ) as client:
                chaos = asyncio.ensure_future(ChaosScenario(
                    fleet, [ChaosEvent(0.2, "kill", 0)]).run())
                status, events, done = await _collect_stream(
                    client, "/v1/completions",
                    {"model": "fake-model", "prompt": "x",
                     "max_tokens": 30, "stream": True})
                await chaos
        finally:
            await fleet.stop()
        # the HTTP status was already committed as 200; the failure has
        # to be in-band and explicit
        assert status == 200 and done
        errs = [e for e in events if "error" in e]
        assert errs and errs[-1]["error"]["type"] == "stream_resume_error"
        after = rm.stream_resumes_total.labels(
            outcome="failed")._value.get()
        assert after == before + 1

    asyncio.run(main())


# -- drill (c): hang → watchdog → readiness → router ejection ----------------

def test_watchdog_hang_flips_readiness_and_router_ejects():
    async def main():
        fleet = ChaosFleet(2, tokens_per_second=2000, ttft=0.001,
                           watchdog_stall_seconds=0.2)
        urls = await fleet.start()
        try:
            await ChaosScenario(
                fleet, [ChaosEvent(0.0, "hang", 0, "1")]).run()
            async with aiohttp.ClientSession() as s:
                # one request must wedge for the stall clock to start
                # (a hang with no victims is indistinguishable from idle)
                doomed = asyncio.ensure_future(s.post(
                    f"{urls[0]}/v1/completions",
                    json={"model": "fake-model", "prompt": "x",
                          "max_tokens": 2}))
                await asyncio.sleep(0.05)
                async with s.get(f"{urls[0]}/ready") as r:
                    assert r.status == 200  # inside the stall window
                await asyncio.sleep(0.3)
                async with s.get(f"{urls[0]}/ready") as r:
                    assert r.status == 503
                    assert (await r.json())["status"] == "stalled"
                async with s.get(f"{urls[0]}/health") as r:
                    assert r.status == 200  # alive for debugging
                doomed.cancel()
                try:
                    await doomed
                except (asyncio.CancelledError, aiohttp.ClientError):
                    pass

            async with _router_client(urls, (
                "--static-backend-health-checks",
                "--health-check-interval", "0.1",
            )) as client:
                from production_stack_tpu.router.service_discovery import (
                    get_service_discovery,
                )

                disc = get_service_discovery()
                deadline = time.monotonic() + 3.0
                while (time.monotonic() < deadline
                       and urls[0] not in disc.draining_urls):
                    await asyncio.sleep(0.02)
                assert urls[0] in disc.draining_urls, \
                    "router never ejected the wedged pod"
                # new work skips the wedged pod entirely — these would
                # hang forever if routed to backend 0
                for i in range(4):
                    r = await client.post(
                        "/v1/completions",
                        json={"model": "fake-model", "prompt": f"q{i}",
                              "max_tokens": 2})
                    assert r.status == 200
                # recovery: clearing the wedge restores readiness and the
                # probe puts the pod back in rotation
                fleet.clear(0)
                async with aiohttp.ClientSession() as s:
                    async with s.get(f"{urls[0]}/ready") as r:
                        assert r.status == 200
                deadline = time.monotonic() + 3.0
                while (time.monotonic() < deadline
                       and urls[0] in disc.draining_urls):
                    await asyncio.sleep(0.02)
                assert urls[0] not in disc.draining_urls
        finally:
            await fleet.stop()

    asyncio.run(main())


# -- real-engine drain: completion, KV hygiene, exit -------------------------

def _real_server(**kwargs):
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
    return EngineServer(cfg, **kwargs)


async def _wait_blocks(server, baseline, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if server.engine.scheduler.num_free_blocks == baseline:
            return
        await asyncio.sleep(0.05)
    raise AssertionError(
        f"KV blocks leaked: {server.engine.scheduler.num_free_blocks} "
        f"free != baseline {baseline}")


def test_real_engine_drain_completes_inflight_and_exits():
    """SIGTERM on a serving engine: in-flight stream runs to completion,
    new work is refused with 503 + Retry-After, readiness goes 503 while
    /health stays 200, drain metrics export, the exit callback fires once
    drained, and every KV block comes back."""
    server = _real_server(drain_deadline=10.0)

    async def main():
        exited = asyncio.Event()
        # observe GracefulExit without killing the test loop
        server._exit = exited.set
        async with TestClient(TestServer(server.build_app())) as c:
            baseline = server.engine.scheduler.num_free_blocks

            # stalled readiness path (watchdog wiring, no real stall)
            server.watchdog.stalled = True
            r = await c.get("/ready")
            assert r.status == 503
            assert (await r.json())["status"] == "stalled"
            server.watchdog.stalled = False
            assert (await c.get("/ready")).status == 200

            stream = asyncio.ensure_future(c.post(
                "/v1/completions",
                json={"prompt": "hello", "max_tokens": 12, "stream": True,
                      "temperature": 0, "ignore_eos": True}))
            await asyncio.sleep(0.05)
            server._on_sigterm()  # in-process: handler invoked directly

            r = await c.get("/ready")
            assert r.status == 503
            body = await r.json()
            assert body["status"] == "draining"
            assert body["reason"] == "sigterm"
            assert (await c.get("/health")).status == 200  # truthful

            r = await c.post("/v1/completions",
                             json={"prompt": "new", "max_tokens": 2})
            assert r.status == 503 and "Retry-After" in r.headers

            r = await c.get("/metrics")
            text = await r.text()
            drain_lines = [l for l in text.splitlines()
                           if l.startswith("vllm:drain_state{")]
            assert drain_lines and drain_lines[0].endswith("1.0")

            sr = await asyncio.wait_for(stream, 30.0)
            assert sr.status == 200
            raw = await sr.read()
            assert b"[DONE]" in raw  # the in-flight stream finished whole

            await asyncio.wait_for(exited.wait(), 15.0)
            assert server._drain_aborted == 0  # nothing needed the axe
            assert server._drain_rejected >= 1
            await _wait_blocks(server, baseline)

    asyncio.run(main())


def test_sigterm_after_api_drain_still_exits():
    """The chart's documented termination order: the preStop hook POSTs
    /drain FIRST, then kubelet delivers SIGTERM. The already-running
    API drain must not swallow the signal — SIGTERM always owns process
    exit, or the pod lingers until terminationGracePeriodSeconds ends in
    SIGKILL (skipping the on_cleanup backend release)."""
    server = _real_server(drain_deadline=10.0)

    async def main():
        exited = asyncio.Event()
        server._exit = exited.set  # observe GracefulExit w/o killing loop
        async with TestClient(TestServer(server.build_app())) as c:
            r = await c.post("/drain")  # the preStop hook fires first
            body = await r.json()
            assert body["status"] == "draining"
            assert not body["already_draining"]
            assert server.drain_reason == "api"
            server._on_sigterm()  # then the kill signal lands
            server._on_sigterm()  # repeated delivery stays idempotent
            await asyncio.wait_for(exited.wait(), 15.0)

    asyncio.run(main())


def test_real_engine_drain_deadline_aborts_stragglers_frees_kv():
    """A straggler that outlives the drain deadline is aborted through
    the abort path — KV blocks are freed, the drain completes bounded."""
    server = _real_server(drain_deadline=0.4)

    async def main():
        async with TestClient(TestServer(server.build_app())) as c:
            baseline = server.engine.scheduler.num_free_blocks
            straggler = asyncio.ensure_future(c.post(
                "/v1/completions",
                json={"prompt": "hello", "max_tokens": 4096,
                      "stream": True, "temperature": 0,
                      "ignore_eos": True}))
            await asyncio.sleep(0.1)
            assert server.begin_drain("test")
            assert not server.begin_drain("test")  # idempotent
            await asyncio.wait_for(server._drain_task, 15.0)
            assert server._drain_aborted >= 1
            await _wait_blocks(server, baseline)
            straggler.cancel()
            try:
                resp = await straggler
                resp.close()
            except (asyncio.CancelledError, aiohttp.ClientError):
                pass

    asyncio.run(main())
