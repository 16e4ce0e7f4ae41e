"""Overload-plane x disaggregation composition: tenant identity and
admission control across the P→D split.

The invariants under test (docs/resilience.md "Overload & fairness"):

- the canonical ``x-tenant-id`` resolved ONCE at router admission rides
  every backend hop, so the prefill and decode engines attribute (and
  fair-share) the same identity the router charged;
- quotas are charged exactly once, at the router — a disaggregated
  request costs two backend hops but one admission;
- fair-share is plain scheduler config, so it applies identically to
  prefill-role and decode-role engines;
- the engine's stage-3 brownout shed refuses NEW work only: a pushed
  P→D continuation (body carrying ``kv_transfer_params.transfer_id``)
  always passes, because shedding it would kill a stream whose prefill
  already ran.
"""

import asyncio
import json

import pytest

from production_stack_tpu.testing.chaos import ChaosFleet


def _router(fleet: ChaosFleet, extra_args=()):
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.router.app import RouterApp, build_parser

    urls = fleet.urls
    args = build_parser().parse_args([
        "--service-discovery", "static",
        "--static-backends", ",".join(urls),
        "--static-models", ",".join(["fake-model"] * len(urls)),
        "--static-backend-roles", ",".join(e.role for e in fleet.engines),
        "--routing-logic", "disaggregated_prefill_orchestrated",
        *extra_args,
    ])
    router = RouterApp(args)
    return router, TestClient(TestServer(router.build_app()))


async def _stream(client, payload, headers=None, timeout=30.0):
    async def _go():
        buf = b""
        async with client.post("/v1/completions", json=payload,
                               headers=headers or {}) as r:
            if r.status != 200:
                return r.status, "", dict(r.headers)
            async for chunk in r.content.iter_any():
                buf += chunk
            resp_headers = dict(r.headers)
        text = ""
        for block in buf.split(b"\n\n"):
            if not block.startswith(b"data: "):
                continue
            data = block[len(b"data: "):]
            if data == b"[DONE]":
                continue
            ev = json.loads(data)
            if ev.get("choices"):
                text += ev["choices"][0].get("text") or ""
        return 200, text, resp_headers

    return await asyncio.wait_for(_go(), timeout)


def _toks(n, first=0):
    return "".join(f"tok{i} " for i in range(first, first + n))


# -- identity rides both hops ------------------------------------------------

def test_streamed_disagg_both_hops_inherit_tenant_header():
    """The streamed pushed-handoff flow: the tenant resolved at the
    router reaches the prefill hop AND the decode continuation as the
    canonical x-tenant-id, and the stream is still bit-identical."""
    n = 6
    payload = {"model": "fake-model", "prompt": "The hedgehog",
               "max_tokens": n, "stream": True, "temperature": 0}

    async def main():
        fleet = ChaosFleet(2, tokens_per_second=500, ttft=0.001,
                           roles=["prefill", "decode"])
        await fleet.start()
        p, d = fleet.engines
        try:
            _, client = _router(fleet)
            async with client:
                status, text, _ = await _stream(
                    client, payload, headers={"x-tenant-id": "acme"})
        finally:
            await fleet.stop()
        assert status == 200 and text == _toks(n)
        # each engine served exactly its own phase, both under "acme"
        assert p.tenants_seen == ["acme"]
        assert d.tenants_seen == ["acme"]

    asyncio.run(main())


def test_nonstream_disagg_pull_flow_inherits_tenant():
    """The buffered (legacy pull) orchestration forwards the same
    canonical header on both hops."""
    payload = {"model": "fake-model", "prompt": "The hedgehog",
               "max_tokens": 4, "temperature": 0}

    async def main():
        fleet = ChaosFleet(2, tokens_per_second=500, ttft=0.001,
                           roles=["prefill", "decode"])
        await fleet.start()
        p, d = fleet.engines
        try:
            _, client = _router(fleet)
            async with client:
                r = await client.post("/v1/completions", json=payload,
                                      headers={"x-tenant-id": "acme"})
                assert r.status == 200, await r.text()
                body = await r.json()
        finally:
            await fleet.stop()
        assert body["choices"][0]["text"] == _toks(4)
        assert p.tenants_seen == ["acme"]
        assert d.tenants_seen == ["acme"]

    asyncio.run(main())


# -- quotas charge once, at the router ---------------------------------------

def test_disagg_quota_charged_once_at_router():
    """A two-hop disaggregated request is ONE admission: with a bucket
    holding exactly 2 request tokens (refill ~0), two disagg requests
    succeed — four backend hops, two charges — and the third 429s with
    a Retry-After. Other tenants ride the unlimited default."""
    n = 4
    payload = {"model": "fake-model", "prompt": "The hedgehog",
               "max_tokens": n, "stream": True, "temperature": 0}
    quota_json = json.dumps(
        {"tenants": {"acme": {"rps": 0.001, "burst_s": 2000.0}}})

    async def main():
        fleet = ChaosFleet(2, tokens_per_second=500, ttft=0.001,
                           roles=["prefill", "decode"])
        await fleet.start()
        p, d = fleet.engines
        try:
            router, client = _router(
                fleet, ["--tenant-quota-config", quota_json])
            async with client:
                for _ in range(2):
                    status, text, _ = await _stream(
                        client, payload, headers={"x-tenant-id": "acme"})
                    assert status == 200 and text == _toks(n)
                # the bucket was debited once per REQUEST, not per hop:
                # 2.0 burst - 2 charges ~= 0 despite 4 backend hops
                rps = router.request_service.quota._buckets["acme"][0]
                assert rps.tokens == pytest.approx(0.0, abs=0.1)
                assert p.total_requests == 2 and d.total_requests == 2

                status, _, headers = await _stream(
                    client, payload, headers={"x-tenant-id": "acme"})
                assert status == 429
                assert float(headers["Retry-After"]) > 0

                # an in-budget tenant is untouched by acme's exhaustion
                status, text, _ = await _stream(
                    client, payload, headers={"x-tenant-id": "calm"})
                assert status == 200 and text == _toks(n)
        finally:
            await fleet.stop()
        # the rejected request never produced a backend hop
        assert p.tenants_seen == ["acme", "acme", "calm"]
        assert d.tenants_seen == ["acme", "acme", "calm"]

    asyncio.run(main())


# -- engine stage-3 shed spares pushed continuations -------------------------

@pytest.fixture(scope="module")
def engine_server():
    from production_stack_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        ModelConfig,
        SchedulerConfig,
    )
    from production_stack_tpu.engine.server import EngineServer
    from production_stack_tpu.parallel.mesh import MeshConfig

    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=512),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_num_batched_tokens=64,
            ),
        mesh=MeshConfig(data=1, tensor=1),
    )
    return EngineServer(cfg)


async def _with_client(server, fn):
    from aiohttp.test_utils import TestClient, TestServer

    async with TestClient(TestServer(server.build_app())) as client:
        return await fn(client)


def test_engine_stage3_shed_spares_pushed_continuations(engine_server):
    """At stage 3 the engine refuses an over-weight tenant's NEW work
    with an honest 429 — but the same tenant's pushed P→D continuation
    (kv_transfer_params.transfer_id) always passes: its prefill already
    ran on another engine, shedding it now would kill a live stream."""
    from production_stack_tpu.engine.overload import (
        BrownoutConfig,
        BrownoutController,
    )

    async def fn(client):
        # installed after app startup so the worker loop never runs and
        # the manually-pinned stage/shed-set stay exactly as written
        ctl = BrownoutController(BrownoutConfig(enabled=True,
                                                interval=3600.0))
        ctl.stage = 3
        engine_server.brownout = ctl
        engine_server._brownout_shed = {"noisy"}
        try:
            body = {"model": "tiny-llama", "prompt": "hello",
                    "max_tokens": 3, "temperature": 0}
            r = await client.post("/v1/completions", json=body,
                                  headers={"x-tenant-id": "noisy"})
            assert r.status == 429
            err = (await r.json())["error"]
            assert "fair share" in err["message"]
            assert float(r.headers["Retry-After"]) > 0
            assert ctl.sheds.get("tenant") == 1

            # the same shed tenant's decode continuation is admitted
            # (unknown transfer id → re-prefill fallback, still serves)
            cont = dict(body)
            cont["kv_transfer_params"] = {"transfer_id": "ghost-1",
                                          "do_remote_decode": False}
            r = await client.post("/v1/completions", json=cont,
                                  headers={"x-tenant-id": "noisy"})
            assert r.status == 200, await r.text()
            assert (await r.json())["usage"]["completion_tokens"] == 3

            # an in-budget tenant admits normally at stage 3
            r = await client.post("/v1/completions", json=body,
                                  headers={"x-tenant-id": "victim"})
            assert r.status == 200, await r.text()
            assert ctl.sheds.get("tenant") == 1  # no further sheds
        finally:
            engine_server.brownout = None
            engine_server._brownout_shed = set()

    asyncio.run(_with_client(engine_server, fn))


def test_engine_stage2_clamps_max_tokens(engine_server):
    """Stage 2 bounds tail work: an over-clamp request is served with
    max_tokens clamped (counted as a max_tokens shed), not refused."""
    from production_stack_tpu.engine.overload import (
        BrownoutConfig,
        BrownoutController,
    )

    async def fn(client):
        ctl = BrownoutController(BrownoutConfig(enabled=True,
                                                interval=3600.0,
                                                max_tokens_clamp=2))
        ctl.stage = 2
        engine_server.brownout = ctl
        try:
            r = await client.post("/v1/completions", json={
                "model": "tiny-llama", "prompt": "hello",
                "max_tokens": 6, "temperature": 0})
            assert r.status == 200, await r.text()
            assert (await r.json())["usage"]["completion_tokens"] == 2
            assert ctl.sheds.get("max_tokens") == 1

            # in-clamp requests are untouched (and not counted)
            r = await client.post("/v1/completions", json={
                "model": "tiny-llama", "prompt": "hello",
                "max_tokens": 2, "temperature": 0})
            assert r.status == 200
            assert (await r.json())["usage"]["completion_tokens"] == 2
            assert ctl.sheds.get("max_tokens") == 1
        finally:
            engine_server.brownout = None

    asyncio.run(_with_client(engine_server, fn))


# -- fair-share is role-agnostic scheduler config ----------------------------

def test_fair_share_flags_apply_on_both_engine_roles():
    """--fair-share/--tenant-weights land in SchedulerConfig the same
    way for prefill-role and decode-role engines: the DRR pass runs on
    whichever phase the role owns."""
    from production_stack_tpu.engine.server import (
        build_parser,
        config_from_args,
    )

    for role in ("prefill", "decode", "unified"):
        args = build_parser().parse_args([
            "--model", "tiny-llama", "--role", role, "--fair-share",
            "--tenant-weights", '{"acme": 3, "basement": 1}',
        ])
        cfg = config_from_args(args)
        assert cfg.role == role
        assert cfg.scheduler.fair_share is True
        assert cfg.scheduler.tenant_weights == {"acme": 3, "basement": 1}
        assert cfg.scheduler.tenant_weight("acme") == 3.0
        assert cfg.scheduler.tenant_weight("unknown") == 1.0
