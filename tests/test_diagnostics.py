"""Anomaly-triggered diagnostic bundles + fleet observability plane.

Three layers, mirroring the subsystem (docs/observability.md
"Diagnostics & incidents"):

* ``DiagnosticsManager`` unit contracts — capture, cooldown,
  single-flight, retention (count and bytes), path-traversal refusal,
  restart re-indexing, best-effort collectors.
* Engine drills through the real ``EngineServer`` over aiohttp: a forced
  post-warmup recompile and an injected watchdog stall each leave an
  indexed, downloadable, retention-bounded bundle.
* Router incidents e2e over a fleet of ``FakeEngine``s: a breaker open /
  stream-resume failure / SLO page opens an incident, captures the
  router bundle and fans correlated captures out to the implicated
  engines; ``GET /debug/fleet`` joins it all and ``tools/stacktop``
  renders it.
"""

import asyncio
import io
import json
import os
import tarfile
import tempfile
import threading
import time
from types import SimpleNamespace

import pytest

from production_stack_tpu.engine.diagnostics import (
    DiagnosticsConfig,
    DiagnosticsManager,
)


def manager(tmp_path, **kw) -> DiagnosticsManager:
    cfg = dict(dir=str(tmp_path / "diag"), cooldown=0.0)
    cfg.update(kw)
    return DiagnosticsManager(
        DiagnosticsConfig(**cfg), tier="engine",
        collectors={"state.json": lambda: {"ok": True}})


# ---------------------------------------------------------------------------
# DiagnosticsManager unit contracts
# ---------------------------------------------------------------------------

def test_sync_capture_writes_indexed_bundle(tmp_path):
    mgr = manager(tmp_path)
    bundle_id = mgr.trigger("unexpected_recompile",
                            {"kind": "decode", "bucket": "b128"}, sync=True)
    assert bundle_id and bundle_id.endswith("unexpected_recompile")

    idx = mgr.index()
    assert idx["enabled"] and idx["tier"] == "engine"
    (row,) = idx["bundles"]
    assert row["id"] == bundle_id
    assert row["trigger"] == "unexpected_recompile"
    assert row["bytes"] > 0
    assert row["detail"]["bucket"] == "b128"

    path = mgr.bundle_path(bundle_id)
    with open(os.path.join(path, "manifest.json")) as f:
        mani = json.load(f)
    assert mani["files"] == ["state.json"]
    assert mani["errors"] == {}
    with open(os.path.join(path, "state.json")) as f:
        assert json.load(f) == {"ok": True}

    # the index's anomaly event tail records the capture
    (event,) = idx["events"]
    assert event["captured"] and event["bundle"] == bundle_id


def test_tar_download_roundtrip(tmp_path):
    mgr = manager(tmp_path)
    bundle_id = mgr.trigger("manual", sync=True)
    data = mgr.tar_bundle(bundle_id)
    with tarfile.open(fileobj=io.BytesIO(data), mode="r:gz") as tar:
        names = tar.getnames()
        assert f"{bundle_id}/manifest.json" in names
        assert f"{bundle_id}/state.json" in names


def test_cooldown_drops_and_force_bypasses(tmp_path):
    mgr = manager(tmp_path, cooldown=3600.0)
    first = mgr.trigger("hbm_pressure", sync=True)
    assert first is not None
    assert mgr.trigger("hbm_pressure", sync=True) is None
    # a DIFFERENT trigger has its own cooldown clock
    assert mgr.trigger("watchdog_stall", sync=True) is not None
    # incident fan-out must never be rate-limited away from its incident
    forced = mgr.trigger("hbm_pressure", force=True, sync=True)
    assert forced is not None and forced != first

    stats = mgr.stats()
    assert stats["dropped_total"] == {"hbm_pressure": 1}
    assert stats["bundles_total"] == {"hbm_pressure": 2, "watchdog_stall": 1}
    dropped = [e for e in mgr.index()["events"] if e.get("dropped")]
    assert dropped and dropped[0]["dropped"] == "cooldown"


def test_single_flight_drops_overlapping_trigger(tmp_path):
    gate = threading.Event()
    entered = threading.Event()

    def slow_collector():
        entered.set()
        gate.wait(5.0)
        return {"slow": True}

    mgr = DiagnosticsManager(
        DiagnosticsConfig(dir=str(tmp_path / "diag"), cooldown=0.0),
        collectors={"slow.json": slow_collector})
    first = mgr.trigger("watchdog_stall")        # async capture thread
    assert first is not None
    assert entered.wait(5.0)
    # a capture is in flight: overlapping triggers drop, never queue
    assert mgr.trigger("watchdog_stall") is None
    assert mgr.trigger("hbm_pressure") is None
    gate.set()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and not mgr.index()["bundles"]:
        time.sleep(0.01)
    assert [b["id"] for b in mgr.index()["bundles"]] == [first]
    assert mgr.stats()["dropped_total"] == {"watchdog_stall": 1,
                                            "hbm_pressure": 1}


def test_retention_bounds_count_then_bytes(tmp_path):
    mgr = manager(tmp_path, max_bundles=3)
    ids = [mgr.trigger("manual", {"n": i}, force=True, sync=True)
           for i in range(6)]
    kept = [b["id"] for b in mgr.index()["bundles"]]
    assert sorted(kept) == sorted(ids[-3:])      # newest 3 survive
    for victim in ids[:3]:
        assert mgr.bundle_path(victim) is None
        assert not os.path.isdir(os.path.join(mgr.dir, victim))

    # byte cap: big payloads evict down to the cap but always keep >= 1
    big = DiagnosticsManager(
        DiagnosticsConfig(dir=str(tmp_path / "big"), cooldown=0.0,
                          max_bundles=100, max_bytes=8 * 1024),
        collectors={"blob.bin": lambda: b"x" * 6 * 1024})
    for _ in range(4):
        big.trigger("manual", force=True, sync=True)
    remaining = big.index()["bundles"]
    assert 1 <= len(remaining) <= 2
    assert sum(b["bytes"] for b in remaining[1:]) <= 8 * 1024


def test_bundle_path_refuses_traversal(tmp_path):
    mgr = manager(tmp_path)
    mgr.trigger("manual", sync=True)
    assert mgr.bundle_path("../../etc/passwd") is None
    assert mgr.bundle_path(".hidden") is None
    assert mgr.tar_bundle("..") is None
    assert mgr.tar_bundle("no-such-bundle") is None


def test_collector_error_is_recorded_not_fatal(tmp_path):
    def boom():
        raise RuntimeError("collector died")

    mgr = DiagnosticsManager(
        DiagnosticsConfig(dir=str(tmp_path / "diag"), cooldown=0.0),
        collectors={"good.json": lambda: {"ok": 1}, "bad.json": boom})
    bundle_id = mgr.trigger("manual", sync=True)
    with open(os.path.join(mgr.bundle_path(bundle_id),
                           "manifest.json")) as f:
        mani = json.load(f)
    assert mani["files"] == ["good.json"]
    assert "RuntimeError" in mani["errors"]["bad.json"]


def test_restart_reindexes_existing_bundles(tmp_path):
    first = manager(tmp_path)
    bundle_id = first.trigger("drain_deadline_abort", sync=True)
    reborn = DiagnosticsManager(
        DiagnosticsConfig(dir=first.dir, cooldown=0.0))
    rows = reborn.index()["bundles"]
    assert [b["id"] for b in rows] == [bundle_id]
    assert reborn.tar_bundle(bundle_id) is not None


def test_note_records_event_without_bundle(tmp_path):
    mgr = manager(tmp_path)
    mgr.note("watchdog_recovered", {"stalls_total": 1})
    idx = mgr.index()
    assert idx["bundles"] == []
    (event,) = idx["events"]
    assert event["trigger"] == "watchdog_recovered"
    assert event["captured"] is False


def test_disabled_manager_never_captures(tmp_path):
    mgr = DiagnosticsManager(
        DiagnosticsConfig(enabled=False, dir=str(tmp_path / "off")))
    assert mgr.trigger("manual", sync=True) is None
    assert not os.path.isdir(str(tmp_path / "off"))


# ---------------------------------------------------------------------------
# Engine drills: real EngineServer, real anomaly signals, HTTP surface
# ---------------------------------------------------------------------------

def engine_server(tmp_path, **server_kw):
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
        scheduler=SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=64),
        mesh=MeshConfig(data=1, tensor=1),
    )
    server_kw.setdefault("diagnostics", DiagnosticsConfig(
        dir=str(tmp_path / "engine-diag"), cooldown=0.0,
        profile_seconds=0.0, max_bundles=2))
    return EngineServer(cfg, **server_kw)


async def wait_for_bundle(client, trigger, deadline=10.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline:
        r = await client.get("/debug/diagnostics")
        idx = await r.json()
        rows = [b for b in idx["bundles"] if b["trigger"] == trigger]
        if rows:
            return idx, rows[0]
        await asyncio.sleep(0.05)
    raise AssertionError(f"no {trigger!r} bundle within {deadline}s")


def test_forced_recompile_drill_leaves_downloadable_bundle(tmp_path):
    """Warmup marks the accountant steady; a fresh compile signature
    after that is the unexpected-recompile bug signal and must leave an
    indexed, downloadable bundle."""
    from aiohttp.test_utils import TestClient, TestServer

    async def main():
        es = engine_server(tmp_path)
        client = TestClient(TestServer(es.build_app()))
        await client.start_server()
        try:
            perf = es.engine.perf
            assert perf is not None
            perf.mark_steady()
            # the leaked shape: a compile the warmup sweep never saw
            perf.on_compile("decode", "bs8", 1.25)
            idx, row = await wait_for_bundle(client, "unexpected_recompile")
            assert row["detail"]["unexpected"] is True
            assert row["detail"]["bucket"] == "bs8"

            r = await client.get(f"/debug/diagnostics/{row['id']}")
            assert r.status == 200
            assert ".tar.gz" in r.headers["Content-Disposition"]
            data = await r.read()
            with tarfile.open(fileobj=io.BytesIO(data), mode="r:gz") as tar:
                names = tar.getnames()
            assert f"{row['id']}/manifest.json" in names
            assert f"{row['id']}/perf.json" in names
            assert f"{row['id']}/compile_events.json" in names
            assert f"{row['id']}/scheduler.json" in names
            buf = io.BytesIO(data)
            with tarfile.open(fileobj=buf, mode="r:gz") as tar:
                # every collector succeeded — in particular scheduler.json,
                # whose perf.compile_counts is tuple-keyed at the source and
                # must be stringified before the JSON dump
                manifest = json.load(
                    tar.extractfile(f"{row['id']}/manifest.json"))
                sched = json.load(
                    tar.extractfile(f"{row['id']}/scheduler.json"))
                # the captured compile tail holds the triggering event
                tail = json.load(
                    tar.extractfile(f"{row['id']}/compile_events.json"))
            assert manifest["errors"] == {}
            assert "decode:bs8" in sched["perf"]["compile_counts"]
            assert any(e["bucket"] == "bs8" and e["unexpected"]
                       for e in tail)

            r = await client.get("/debug/diagnostics/missing-bundle")
            assert r.status == 404
        finally:
            await client.close()

    asyncio.run(main())


def test_watchdog_stall_drill_captures_then_notes_recovery(tmp_path):
    """Drive the stuck-step detector with a synthetic clock: the stall
    transition captures a bundle, the recovery only notes an event (the
    evidence was captured at the stall)."""
    from aiohttp.test_utils import TestClient, TestServer

    async def main():
        es = engine_server(tmp_path, watchdog_stall_seconds=5.0)
        client = TestClient(TestServer(es.build_app()))
        await client.start_server()
        try:
            wd = es.watchdog
            stub = SimpleNamespace(
                step_count=7, paused=False,
                engine=SimpleNamespace(has_unfinished=lambda: True))
            wd.async_engine = stub
            assert wd.check(100.0) is False     # first look: baseline
            assert wd.check(106.0) is True      # 6s, no progress: stall
            idx, row = await wait_for_bundle(client, "watchdog_stall")
            assert row["detail"]["stalls_total"] == 1

            stub.step_count = 8                 # scheduler moved again
            assert wd.check(107.0) is False
            events = (await (await client.get(
                "/debug/diagnostics")).json())["events"]
            recov = [e for e in events
                     if e["trigger"] == "watchdog_recovered"]
            assert recov and recov[0]["captured"] is False
            # recovery produced NO second bundle
            idx = await (await client.get("/debug/diagnostics")).json()
            assert [b["trigger"] for b in idx["bundles"]] == \
                ["watchdog_stall"]
        finally:
            await client.close()

    asyncio.run(main())


def test_capture_endpoint_and_retention_over_http(tmp_path):
    """POST /debug/diagnostics/capture answers only once the bundle is
    on disk; the archive stays bounded at max_bundles across captures."""
    from aiohttp.test_utils import TestClient, TestServer

    async def main():
        es = engine_server(tmp_path)            # max_bundles=2
        client = TestClient(TestServer(es.build_app()))
        await client.start_server()
        try:
            ids = []
            for i in range(4):
                r = await client.post(
                    "/debug/diagnostics/capture",
                    json={"trigger": "manual",
                          "incident": f"inc-{i}",
                          "detail": {"n": i}})
                assert r.status == 200
                body = await r.json()
                assert body["captured"] is True
                # deterministic: the bundle is on disk at response time
                assert es.diagnostics.bundle_path(body["bundle"])
                ids.append(body["bundle"])
            idx = await (await client.get("/debug/diagnostics")).json()
            kept = [b["id"] for b in idx["bundles"]]
            assert sorted(kept) == sorted(ids[-2:])
            assert idx["bundles"][0]["detail"]["incident"] == "inc-3"
        finally:
            await client.close()

    asyncio.run(main())


# ---------------------------------------------------------------------------
# Router incidents + fleet plane over a FakeEngine fleet
# ---------------------------------------------------------------------------

async def fake_fleet(n):
    from aiohttp.test_utils import TestServer

    from production_stack_tpu.testing.fake_engine import FakeEngine

    engines, servers, urls = [], [], []
    for _ in range(n):
        fe = FakeEngine(model="fake-model", tokens_per_second=500,
                        ttft=0.001)
        ts = TestServer(fe.build_app())
        await ts.start_server()
        engines.append(fe)
        servers.append(ts)
        urls.append(f"http://127.0.0.1:{ts.port}")
    return engines, servers, urls


async def fleet_router(urls, extra_args=()):
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.router.app import RouterApp, build_parser

    args = build_parser().parse_args([
        "--service-discovery", "static",
        "--static-backends", ",".join(urls),
        "--static-models", ",".join(["fake-model"] * len(urls)),
        "--diagnostics-dir", tempfile.mkdtemp(prefix="router-diag-"),
        *extra_args,
    ])
    router = RouterApp(args)
    client = TestClient(TestServer(router.build_app()))
    await client.start_server()
    return router, client


async def wait_until(predicate, deadline=10.0, msg="condition"):
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline:
        if predicate():
            return
        await asyncio.sleep(0.05)
    raise AssertionError(f"timed out waiting for {msg}")


def test_incident_fanout_captures_on_every_implicated_engine(tmp_path):
    """An incident over a 3-engine fleet fans POST .../capture out to
    every implicated engine; each answers with a real bundle id that is
    on that engine's disk, carrying the incident id."""
    from production_stack_tpu.router.incidents import (
        current_incident_manager,
    )

    async def main():
        engines, servers, urls = await fake_fleet(3)
        router, client = await fleet_router(urls)
        try:
            im = current_incident_manager()
            assert im is not None and im.config.enabled
            inc = im.open_incident("burn_rate_page",
                                   "slo_page:fake-model:ttft_p95",
                                   window={"model": "fake-model"},
                                   implicated=list(urls))
            assert inc.bundle is not None       # router-tier bundle
            await wait_until(lambda: len(inc.engine_bundles) == 3,
                             msg="engine capture fan-out")
            for fe, url in zip(engines, urls):
                bundle_id = inc.engine_bundles[url]
                assert not bundle_id.startswith("error"), bundle_id
                assert bundle_id.endswith("incident_burn_rate_page")
                path = fe.diagnostics.bundle_path(bundle_id)
                assert path is not None
                with open(os.path.join(path, "manifest.json")) as f:
                    mani = json.load(f)
                assert mani["detail"]["incident"] == inc.id
                # and the engine's own index serves it
                idx = await (await client.session.get(
                    f"{url}/debug/diagnostics")).json()
                assert bundle_id in [b["id"] for b in idx["bundles"]]

            # idempotent while open: the same key re-touches, no dup
            again = im.open_incident("burn_rate_page",
                                     "slo_page:fake-model:ttft_p95",
                                     window={"touch": 2})
            assert again.id == inc.id and again.window["touch"] == 2
            assert im.snapshot()["open"] == 1

            # the router's own debug surface joins incidents + bundles
            dbg = await (await client.get("/debug/diagnostics")).json()
            assert dbg["incidents"]["open"] == 1
            assert dbg["incidents"]["incidents"][0]["id"] == inc.id
            assert any(b["id"] == inc.bundle
                       for b in dbg["bundles"]["bundles"])
            r = await client.get(f"/debug/diagnostics/{inc.bundle}")
            assert r.status == 200
            with tarfile.open(fileobj=io.BytesIO(await r.read()),
                              mode="r:gz") as tar:
                assert f"{inc.bundle}/slo.json" in tar.getnames()

            im.close_incident("slo_page:fake-model:ttft_p95",
                              "burn rate recovered")
            assert im.snapshot()["open"] == 0
        finally:
            await client.close()
            for ts in servers:
                await ts.close()

    asyncio.run(main())


def test_breaker_and_stream_resume_incident_lifecycle(tmp_path):
    from production_stack_tpu.router.incidents import (
        current_incident_manager,
    )

    async def main():
        engines, servers, urls = await fake_fleet(3)
        router, client = await fleet_router(urls)
        try:
            im = current_incident_manager()
            im.on_breaker_state(urls[0], 2)     # OPEN → incident
            assert im.snapshot()["open"] == 1
            (row,) = [i for i in im.snapshot()["incidents"]
                      if i["status"] == "open"]
            assert row["trigger"] == "breaker_open"
            assert row["implicated"] == [urls[0]]
            assert im.open_incidents_for(urls[0]) == [row["id"]]
            assert im.open_incidents_for(urls[1]) == []
            im.on_breaker_state(urls[0], 2)     # still open: no dup
            assert im.snapshot()["open"] == 1
            im.on_breaker_state(urls[0], 0)     # CLOSED → resolves
            assert im.snapshot()["open"] == 0

            # a lost stream opens-and-closes: recorded, never dangling
            inc = im.on_stream_resume_failure("budget_exhausted",
                                              urls[1], "fake-model")
            assert inc.status == "closed"
            assert inc.close_reason == "stream loss recorded"
            assert im.snapshot()["open"] == 0
            rows = {i["id"]: i for i in im.snapshot()["incidents"]}
            assert rows[inc.id]["window"]["outcome"] == "budget_exhausted"
            await wait_until(lambda: urls[1] in inc.engine_bundles,
                             msg="stream-resume engine capture")
        finally:
            await client.close()
            for ts in servers:
                await ts.close()

    asyncio.run(main())


def test_debug_fleet_joins_engines_and_stacktop_renders_it(tmp_path):
    """GET /debug/fleet returns one row per engine with perf + readiness
    joined in; tools/stacktop renders the snapshot into the fleet table."""
    from production_stack_tpu.router.incidents import (
        current_incident_manager,
    )
    from tools.stacktop import render_table

    async def main():
        engines, servers, urls = await fake_fleet(3)
        engines[2].draining = True              # one sick engine
        router, client = await fleet_router(urls)
        try:
            im = current_incident_manager()
            im.on_breaker_state(urls[0], 2)
            r = await client.get("/debug/fleet")
            assert r.status == 200
            snap = await r.json()
            rows = {row["url"]: row for row in snap["engines"]}
            assert set(rows) == set(urls)
            ready = rows[urls[0]]
            assert ready["status"] == "ready"
            assert ready["models"] == ["fake-model"]
            assert ready["mfu"] == pytest.approx(0.42)
            assert ready["hbm_total_bytes"] == 16 * 1024 ** 3
            assert ready["unexpected_recompiles"] == 0
            assert rows[urls[2]]["status"] == "draining"
            # the open breaker incident is attached to its engine row
            assert ready["incidents"] == \
                im.open_incidents_for(urls[0])
            assert snap["router"]["incidents"]["open"] == 1

            table = render_table(snap)
            for url in urls:
                assert url.replace("http://", "")[:20] in table
            assert "ready" in table and "draining" in table
            assert "42.0%" in table             # the fake fleet's MFU
            assert "incidents open: 1" in table
            assert "breaker_open" in table
        finally:
            await client.close()
            for ts in servers:
                await ts.close()

    asyncio.run(main())


def test_fleet_marks_unreachable_engine(tmp_path):
    async def main():
        engines, servers, urls = await fake_fleet(2)
        await servers[1].close()                # kill one engine
        router, client = await fleet_router(urls)
        try:
            snap = await (await client.get("/debug/fleet")).json()
            rows = {row["url"]: row for row in snap["engines"]}
            assert rows[urls[0]]["status"] == "ready"
            dead = rows[urls[1]]
            assert dead["status"] not in ("ready", None)
            assert dead["mfu"] is None
        finally:
            await client.close()
            await servers[0].close()

    asyncio.run(main())


def test_stacktop_render_is_pure_and_stable():
    """Snapshot test: the renderer is a pure function of the /debug/fleet
    document, so stacktop --watch can never disturb the fleet."""
    from tools.stacktop import render_table

    snap = {
        "ts": 1754300000.0,
        "engines": [{
            "url": "http://eng-0:8000", "models": ["llama-3-8b"],
            "label": "llama", "status": "ready", "draining": False,
            "warming": False, "watchdog_stalled": False,
            "mfu": 0.315, "hbm_used_bytes": 12 * 1024 ** 3,
            "hbm_total_bytes": 16 * 1024 ** 3, "kv_usage": 0.25,
            # waiting/running arrive as floats off the prometheus scrape
            "kv_free": 0.75, "waiting": 3.0, "running": 2.0, "qps": 12.5,
            "ttft": 0.21, "tokens_per_second": {"decode": 900.0},
            "unexpected_recompiles": 0, "incidents": ["inc-abc123"],
        }],
        "router": {
            "slo": {"series": [{"model": "llama-3-8b", "slo": "ttft_p95",
                                "page": True}]},
            "scale": {"models": {"llama-3-8b":
                                 {"desired_replicas": 4}}},
            "incidents": {"open": 1, "incidents": [{
                "id": "inc-abc123", "trigger": "burn_rate_page",
                "status": "open", "opened": 1754299990.0,
                "key": "slo_page:llama-3-8b:ttft_p95"}]},
        },
    }
    table = render_table(snap)
    assert "eng-0:8000" in table
    assert "llama" in table
    assert "31.5%" in table                     # MFU formatting
    assert "12.0/16.0G" in table                # HBM used/total in GiB
    assert "inc-abc123" in table
    assert "incidents open: 1" in table
    assert "burn_rate_page" in table
    assert "llama-3-8b/ttft_p95" in table       # paged SLO series
    assert "llama-3-8b" in table and "4" in table  # scale line
    # pure: same input, same output
    assert render_table(json.loads(json.dumps(snap))) == table
