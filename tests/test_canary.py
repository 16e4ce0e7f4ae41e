"""Correctness canary plane (docs/observability.md "Correctness
canaries").

Four layers, mirroring the subsystem:

* Golden-store unit contracts — the two-part comparison (exact greedy
  token identity, top-k logprob fingerprint under a per-record
  L-infinity tolerance band), version bumps, disk round trips.
* Engine capture surface — ``GET /debug/canary`` on both tiers: the
  fake's deterministic pseudo-logprob path (so goldens from one fake
  match any clean fake of the same model) with the numeric-fault knobs
  (``logit_noise_scale``, ``wrong_token_at_step``) changing exactly
  what a real drifted engine would change, and the real ``EngineServer``
  golden → live-probe → exact-match round trip on the CPU backend.
* Router prober e2e over a FakeEngine fleet — probes traverse the full
  serving path (a real POST against the router's own surface), feed the
  availability SLO, detect an armed drift within 3 rounds, open exactly
  one ``canary_drift`` incident fanning bundle capture to the
  implicated engines, close it on recovery, and survive a 50-round
  clean soak with zero false positives.
* Observe-only by construction — a canary-on run leaves tenant usage
  rows and quota buckets identical to a canary-off run; plus the
  stacktop/canaryctl operator surfaces.
"""

import asyncio
import json
import math
import tempfile
import threading
import time
from types import SimpleNamespace

import aiohttp
import pytest
from aiohttp import web

from production_stack_tpu.canary_golden import (
    DEFAULT_PROBES,
    GoldenRecord,
    GoldenStore,
    compare,
    diff_records,
    fingerprint_of,
    probe_by_id,
    record_from_response,
)

MODEL = "fake-model"


# ---------------------------------------------------------------------------
# Golden-store unit contracts
# ---------------------------------------------------------------------------

def _fp(tokens, shift=0.0):
    return [{t: -0.1 + shift, f"alt{i} ": -2.0 - i}
            for i, t in enumerate(tokens)]


def _golden(tokens=None, fingerprint=None, **kw):
    tokens = list(tokens if tokens is not None else ["a ", "b ", "c "])
    if fingerprint is None:
        fingerprint = _fp(tokens)
    d = dict(model=MODEL, probe="greedy-prose", prompt="p", tokens=tokens,
             fingerprint=fingerprint)
    d.update(kw)
    return GoldenRecord(**d)


def test_compare_exact_match_passes():
    rec = _golden()
    v = compare(rec, list(rec.tokens),
                [dict(f) for f in rec.fingerprint])
    assert v.ok and v.kind == "" and v.linf == 0.0


def test_compare_flags_greedy_token_divergence():
    rec = _golden()
    tokens = list(rec.tokens)
    tokens[1] = "WRONG "
    v = compare(rec, tokens, _fp(tokens))
    assert not v.ok and v.kind == "token" and v.first_divergence == 1
    assert "WRONG" in v.detail
    # a truncated stream diverges at the first missing step
    v = compare(rec, rec.tokens[:2], rec.fingerprint[:2])
    assert not v.ok and v.kind == "token" and v.first_divergence == 2


def test_compare_fingerprint_tolerance_band_is_per_record():
    rec = _golden()
    drifted = [dict(f) for f in rec.fingerprint]
    drifted[2][rec.tokens[2]] += 0.3
    # bf16-style record: tolerance 0.0 → any movement is drift
    v = compare(rec, list(rec.tokens), drifted)
    assert not v.ok and v.kind == "fingerprint"
    assert v.linf == pytest.approx(0.3) and v.first_divergence == 2
    # quantized-style record: a 0.5 band admits the same response
    banded = _golden(tolerance=0.5)
    v = compare(banded, list(banded.tokens), drifted)
    assert v.ok and v.linf == pytest.approx(0.3)


def test_compare_disjoint_topk_sets_are_immediate_drift():
    rec = _golden()
    moved = [dict(f) for f in rec.fingerprint]
    moved[1] = {"x ": -0.1, "y ": -0.2}   # candidate set fully rotated
    v = compare(rec, list(rec.tokens), moved)
    assert not v.ok and v.kind == "fingerprint"
    assert math.isinf(v.linf) and v.first_divergence == 1


def test_compare_missing_logprobs():
    rec = _golden()
    v = compare(rec, [], [])
    assert not v.ok and v.kind == "missing_logprobs"
    # tokens present but no comparable top-k entries anywhere
    v = compare(rec, list(rec.tokens), [None] * len(rec.tokens))
    assert not v.ok and v.kind == "missing_logprobs"


def test_fingerprint_of_tolerates_partial_blocks():
    assert fingerprint_of(None) == ([], [])
    tokens, fp = fingerprint_of({
        "tokens": ["a", "b", "c"],
        "token_logprobs": [-0.1, -0.2, -0.3],
        "top_logprobs": [{"a": -0.1}, None],
    })
    assert tokens == ["a", "b", "c"]
    assert fp == [{"a": -0.1}, None, None]   # padded to len(tokens)


def test_record_from_response_requires_logprobs():
    probe = probe_by_id("greedy-prose")
    with pytest.raises(ValueError):
        record_from_response(MODEL, probe, {"choices": []})
    with pytest.raises(ValueError):
        record_from_response(
            MODEL, probe, {"choices": [{"text": "x", "logprobs": None}]})


def test_store_version_bump_and_disk_roundtrip(tmp_path):
    path = str(tmp_path / "golden.json")
    store = GoldenStore(path=path)
    first = store.put(_golden())
    assert first.version == 1
    # unchanged re-record keeps the version
    assert store.put(_golden()).version == 1
    # a changed capture bumps it
    moved = _golden(fingerprint=_fp(["a ", "b ", "c "], shift=0.25))
    assert store.put(moved).version == 2
    # a tolerance change alone is also a new golden (the band is policy)
    assert store.put(_golden(fingerprint=_fp(["a ", "b ", "c "], shift=0.25),
                             tolerance=0.4)).version == 3
    store.save()

    loaded = GoldenStore.load(path)
    rec = loaded.lookup(MODEL, "greedy-prose")
    assert rec is not None and rec.version == 3
    assert rec.tolerance == 0.4
    assert rec.tokens == ["a ", "b ", "c "]
    assert loaded.models() == [MODEL]
    (row,) = loaded.snapshot()["records"]
    assert row["version"] == 3 and row["tokens"] == 3
    # missing file → empty store (availability-only probing), not a crash
    assert GoldenStore.load(str(tmp_path / "absent.json")).records == {}


def test_diff_records_reports_drift():
    a = _golden(version=1)
    same = diff_records(a, _golden(version=2))
    assert same["tokens_identical"] and same["within_tolerance"]
    assert same["linf"] == 0.0 and same["versions"] == [1, 2]
    moved = _golden(fingerprint=_fp(["a ", "b ", "c "], shift=0.2),
                    version=2)
    d = diff_records(a, moved)
    assert d["tokens_identical"] and not d["within_tolerance"]
    assert d["linf"] == pytest.approx(0.2)


def test_canary_config_from_args():
    from production_stack_tpu.router.canary import CanaryConfig

    assert CanaryConfig.from_args(SimpleNamespace(canary=False)) is None
    cfg = CanaryConfig.from_args(SimpleNamespace(
        canary=True, host="0.0.0.0", port=9101, canary_interval=5.0,
        canary_golden_path="/tmp/g.json", canary_timeout=10.0,
        canary_target=""))
    # a wildcard bind self-probes over loopback
    assert cfg.target == "http://127.0.0.1:9101"
    assert cfg.interval == 5.0 and cfg.golden_path == "/tmp/g.json"
    cfg = CanaryConfig.from_args(SimpleNamespace(
        canary=True, host="10.0.0.4", port=8001, canary_interval=30.0,
        canary_golden_path="", canary_timeout=30.0,
        canary_target="http://lb:9999"))
    assert cfg.target == "http://lb:9999"


# ---------------------------------------------------------------------------
# SLO no-data windows + the reserved-tenant carve-out (satellites)
# ---------------------------------------------------------------------------

def test_slo_no_data_windows_are_omitted_not_stale_zero():
    from production_stack_tpu.router import metrics as m
    from production_stack_tpu.router.slo import SLOConfig, SLOTracker

    model = "canary-slo-unit"
    tracker = SLOTracker(SLOConfig(availability=0.999))
    now = time.time()
    # one attempt 33 minutes ago: inside 1h/6h, outside 5m/30m
    tracker.record_attempt(model, True, now - 2000)
    obs = tracker.window_observations(model, "availability", now)
    assert obs["5m"] == 0 and obs["30m"] == 0
    assert obs["1h"] == 1 and obs["6h"] == 1

    (row,) = tracker.snapshot(now)["series"]
    assert row["burn_rate"]["5m"] is None      # no data ≠ healthy
    assert row["burn_rate"]["1h"] == 0.0

    def burn_windows():
        return {(s.labels["model"], s.labels["window"])
                for metric in m.slo_burn_rate.collect()
                for s in metric.samples if s.labels["model"] == model}

    m.refresh_slo_gauges(tracker)
    assert (model, "1h") in burn_windows()
    assert (model, "5m") not in burn_windows()
    # a fresh observation brings the fast windows back
    tracker.record_attempt(model, True, now)
    m.refresh_slo_gauges(tracker)
    assert (model, "5m") in burn_windows()
    # and a tracker without the series removes the stale labels
    m.refresh_slo_gauges(SLOTracker(SLOConfig(availability=0.999)))
    assert burn_windows() == set()


def test_tenant_tracker_reserves_the_canary_identity():
    from production_stack_tpu.router.slo import TenantUsageTracker
    from production_stack_tpu.tenancy import CANARY_TENANT

    tracker = TenantUsageTracker(top_k=1)
    now = time.time()
    for i in range(tracker.cap):
        tracker.record_request(f"t{i:03d}", now)
    tracker.record_request("late-tenant", now)     # over cap → other
    tracker.record_request(CANARY_TENANT, now)     # reserved: never folds

    rows = tracker.usage_rows(now=now)
    assert CANARY_TENANT in rows and rows[CANARY_TENANT]["requests"] == 1
    assert "late-tenant" not in rows

    snap = tracker.snapshot(now=now)["tenants"]
    # folded to top_k=1 the canary row still stands alone — synthetic
    # probe usage must never contaminate real tenants' folded rows
    assert CANARY_TENANT in snap
    assert snap[CANARY_TENANT]["requests"] == 1


# ---------------------------------------------------------------------------
# Fake-engine capture surface + numeric-fault knobs
# ---------------------------------------------------------------------------

async def _fake_client(fe):
    from aiohttp.test_utils import TestClient, TestServer

    client = TestClient(TestServer(fe.build_app()))
    await client.start_server()
    return client


def _strip_stamps(records):
    return [{k: v for k, v in r.items() if k not in ("created",)}
            for r in records]


def test_fake_capture_is_deterministic_per_model():
    from production_stack_tpu.testing.fake_engine import FakeEngine

    async def main():
        clients = []
        try:
            docs = []
            for fe in (FakeEngine(model=MODEL), FakeEngine(model=MODEL),
                       FakeEngine(model="other-model")):
                client = await _fake_client(fe)
                clients.append(client)
                docs.append(await (await client.get("/debug/canary")).json())
            a, b, other = docs
            assert not a["errors"]
            assert len(a["records"]) == len(DEFAULT_PROBES)
            # two clean fakes of the same model capture the SAME goldens
            # (the bit-identity a real bf16 fleet promises)
            assert _strip_stamps(a["records"]) == _strip_stamps(b["records"])
            # a different model has different numerics
            assert (a["records"][0]["fingerprint"]
                    != other["records"][0]["fingerprint"])
            # tolerance stamping for quantized-fleet captures
            doc = await (await clients[0].get(
                "/debug/canary?tolerance=0.25")).json()
            assert all(r["tolerance"] == 0.25 for r in doc["records"])
            r = await clients[0].get("/debug/canary?tolerance=abc")
            assert r.status == 400
        finally:
            for client in clients:
                await client.close()

    asyncio.run(main())


def test_fake_numeric_fault_knobs_change_the_capture():
    from production_stack_tpu.testing.fake_engine import FakeEngine
    from production_stack_tpu.testing.faults import FaultSpec

    async def main():
        fe = FakeEngine(model=MODEL)
        client = await _fake_client(fe)
        try:
            async def capture():
                doc = await (await client.get("/debug/canary")).json()
                return [GoldenRecord.from_dict(r) for r in doc["records"]]

            clean = await capture()

            # logit noise: same greedy tokens, moved fingerprint — the
            # silent-drift failure mode, guaranteed to trip a
            # 0-tolerance golden (perturbation floor is 0.5 * scale)
            fe.fault_state.set(FaultSpec.parse("logit_noise_scale=0.25"))
            noisy = await capture()
            for g, n in zip(clean, noisy):
                assert n.tokens == g.tokens
                v = compare(g, n.tokens, n.fingerprint)
                assert not v.ok and v.kind == "fingerprint"
                assert v.linf >= 0.125

            # wrong token: the argmax itself flips at one step, in both
            # the text and the fingerprint
            fe.fault_state.set(FaultSpec.parse("wrong_token_at_step=2"))
            wrong = await capture()
            for g, w in zip(clean, wrong):
                assert w.tokens != g.tokens
                v = compare(g, w.tokens, w.fingerprint)
                assert not v.ok and v.kind == "token"
                assert v.first_divergence == 2

            # clearing the fault restores bit-identity
            fe.fault_state.set(None)
            healed = await capture()
            for g, h in zip(clean, healed):
                assert compare(g, h.tokens, h.fingerprint).ok
        finally:
            await client.close()

    asyncio.run(main())


def test_fake_golden_probe_roundtrip_through_completions():
    """The acceptance round trip on the fake tier: a golden captured
    from /debug/canary exactly matches what the probe body gets back
    from the serving endpoint itself."""
    from production_stack_tpu.testing.fake_engine import FakeEngine

    async def main():
        fe = FakeEngine(model=MODEL)
        client = await _fake_client(fe)
        try:
            doc = await (await client.get("/debug/canary")).json()
            for raw in doc["records"]:
                rec = GoldenRecord.from_dict(raw)
                probe = probe_by_id(rec.probe)
                r = await client.post("/v1/completions",
                                      json=probe.request_body(MODEL))
                assert r.status == 200
                payload = await r.json()
                tokens, fp = fingerprint_of(
                    payload["choices"][0]["logprobs"])
                v = compare(rec, tokens, fp)
                assert v.ok and v.linf == 0.0, v.detail
        finally:
            await client.close()

    asyncio.run(main())


def test_chaos_drift_action_arms_the_numeric_faults():
    from production_stack_tpu.testing import chaos as chaos_mod

    assert "drift" in chaos_mod.ChaosEvent._ACTIONS
    fleet = chaos_mod.ChaosFleet(2)
    fleet.drift(1)                                   # bare default scale
    assert fleet.engines[1].fault_state.spec.logit_noise_scale == 0.5
    fleet.drift(1, "0.125")                          # bare scale
    assert fleet.engines[1].fault_state.spec.logit_noise_scale == 0.125
    fleet.drift(1, "wrong_token_at_step=3")          # full spec string
    assert fleet.engines[1].fault_state.spec.wrong_token_at_step == 3
    fleet.clear(1)
    assert fleet.engines[1].fault_state.spec is None
    assert fleet.engines[0].fault_state.spec is None  # untouched


# ---------------------------------------------------------------------------
# Real engine tier: /debug/canary capture + live-probe exact match
# ---------------------------------------------------------------------------

def test_real_engine_golden_probe_roundtrip(tmp_path):
    """The real EngineServer's capture surface answers golden records
    from its own sampling path, capture is deterministic, and a live
    /v1/completions probe matches the capture bit-exactly."""
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        ModelConfig,
        SchedulerConfig,
    )
    from production_stack_tpu.engine.diagnostics import DiagnosticsConfig
    from production_stack_tpu.engine.server import EngineServer
    from production_stack_tpu.parallel.mesh import MeshConfig

    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=512),
        scheduler=SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=64),
        mesh=MeshConfig(data=1, tensor=1),
    )
    es = EngineServer(cfg, diagnostics=DiagnosticsConfig(
        dir=str(tmp_path / "diag"), cooldown=0.0, profile_seconds=0.0,
        max_bundles=2))

    async def main():
        client = TestClient(TestServer(es.build_app()))
        await client.start_server()
        try:
            # first-ever generation runs the cold compile path, whose
            # numerics can sit ~1e-6 off steady state — the reason
            # canaryctl documents recording from a WARMED engine
            warm = await client.get("/debug/canary")
            assert warm.status == 200

            r = await client.get("/debug/canary")
            assert r.status == 200
            doc = await r.json()
            assert doc["errors"] == []
            assert len(doc["records"]) == len(DEFAULT_PROBES)
            again = await (await client.get("/debug/canary")).json()
            assert (_strip_stamps(doc["records"])
                    == _strip_stamps(again["records"]))
            for raw in doc["records"]:
                rec = GoldenRecord.from_dict(raw)
                assert rec.tokens and len(rec.fingerprint) == len(rec.tokens)
                assert rec.source.startswith("engine:")
                probe = probe_by_id(rec.probe)
                r = await client.post("/v1/completions",
                                      json=probe.request_body(rec.model))
                assert r.status == 200
                payload = await r.json()
                tokens, fp = fingerprint_of(
                    payload["choices"][0]["logprobs"])
                v = compare(rec, tokens, fp)
                assert v.ok and v.linf == 0.0, v.detail
        finally:
            await client.close()

    asyncio.run(main())


# ---------------------------------------------------------------------------
# Router prober e2e over a FakeEngine fleet
# ---------------------------------------------------------------------------

async def _fleet(n):
    from aiohttp.test_utils import TestServer

    from production_stack_tpu.testing.fake_engine import FakeEngine

    engines, servers, urls = [], [], []
    for _ in range(n):
        fe = FakeEngine(model=MODEL, tokens_per_second=500, ttft=0.001)
        ts = TestServer(fe.build_app())
        await ts.start_server()
        engines.append(fe)
        servers.append(ts)
        urls.append(f"http://127.0.0.1:{ts.port}")
    return engines, servers, urls


async def _seed_goldens(url, path):
    async with aiohttp.ClientSession() as session:
        async with session.get(f"{url}/debug/canary") as r:
            doc = await r.json()
    store = GoldenStore(path=path)
    for raw in doc["records"]:
        store.put(GoldenRecord.from_dict(raw))
    store.save()
    return store


async def _canary_router(urls, golden_path="", extra=()):
    """fleet_router with the canary plane on, driven manually: the
    background worker is cancelled and the probe target pointed at the
    TestClient's socket, so tests count rounds deterministically while
    probes still traverse the router's full serving surface."""
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.router.app import RouterApp, build_parser
    from production_stack_tpu.router.canary import current_canary_prober

    flags = ["--canary", "--canary-interval", "3600"]
    if golden_path:
        flags += ["--canary-golden-path", golden_path]
    args = build_parser().parse_args([
        "--service-discovery", "static",
        "--static-backends", ",".join(urls),
        "--static-models", ",".join([MODEL] * len(urls)),
        "--diagnostics-dir", tempfile.mkdtemp(prefix="router-diag-"),
        *flags, *extra,
    ])
    router = RouterApp(args)
    client = TestClient(TestServer(router.build_app()))
    await client.start_server()
    prober = current_canary_prober()
    assert prober is not None
    if router._canary_task is not None:
        router._canary_task.cancel()
    prober.config.target = str(client.make_url("")).rstrip("/")
    return router, client, prober


def _probe_count(outcome):
    from production_stack_tpu.router import metrics as m

    return m.canary_probes_total.labels(
        model=MODEL, outcome=outcome)._value.get()


def _fail_count(kind):
    from production_stack_tpu.router import metrics as m

    return m.canary_identity_failures_total.labels(
        model=MODEL, kind=kind)._value.get()


async def _teardown(client, servers):
    await client.close()
    for ts in servers:
        await ts.close()


async def _wait(predicate, deadline=10.0, msg="condition"):
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline:
        if predicate():
            return
        await asyncio.sleep(0.05)
    raise AssertionError(f"timed out waiting for {msg}")


def test_prober_ok_round_feeds_slo_and_every_surface(tmp_path):
    from production_stack_tpu.router.slo import current_slo_tracker
    from production_stack_tpu.tenancy import CANARY_TENANT

    async def main():
        engines, servers, urls = await _fleet(2)
        golden_path = str(tmp_path / "golden.json")
        await _seed_goldens(urls[0], golden_path)
        router, client, prober = await _canary_router(
            urls, golden_path, extra=("--slo-availability", "0.999"))
        try:
            ok0 = _probe_count("ok")
            await prober.run_round()

            assert len(prober.state) == len(DEFAULT_PROBES)
            for st in prober.state.values():
                assert st.outcome == "ok" and st.kind == ""
                assert st.linf == 0.0 and st.golden_version == 1
                assert st.role_path == "unified" and st.failures == 0
            assert _probe_count("ok") == ok0 + len(DEFAULT_PROBES)

            # the availability feed: an otherwise-idle model has live
            # observations in the fast windows — no stale-zero burn
            tracker = current_slo_tracker()
            obs = tracker.window_observations(MODEL, "availability")
            assert obs["5m"] >= len(DEFAULT_PROBES)

            # probes really traversed the serving path, attributed to
            # the reserved canary tenant on every hop
            assert any(CANARY_TENANT in fe.tenants_seen for fe in engines)

            # router debug surface
            doc = await (await client.get("/debug/canary")).json()
            assert doc["enabled"] and doc["rounds"] == 1
            assert len(doc["golden"]["records"]) == len(DEFAULT_PROBES)
            assert all(p["outcome"] == "ok" for p in doc["probes"])

            # fleet join + stacktop render
            from tools.stacktop import _fmt_canary, render_canary

            fleet_doc = await (await client.get("/debug/fleet")).json()
            assert fleet_doc["router"]["canary"]["enabled"]
            for row in fleet_doc["engines"]:
                assert row["canary"]["outcome"] == "ok"
                assert _fmt_canary(row).startswith("ok")
            table = render_canary(fleet_doc)
            assert "greedy-prose" in table and "v1" in table

            summary = prober.model_summary()
            assert summary[MODEL]["outcome"] == "ok"
        finally:
            await _teardown(client, servers)

    asyncio.run(main())


def test_prober_without_goldens_probes_for_availability(tmp_path):
    from production_stack_tpu.router.incidents import (
        current_incident_manager,
    )

    async def main():
        engines, servers, urls = await _fleet(1)
        router, client, prober = await _canary_router(urls)
        try:
            ng0 = _probe_count("no_golden")
            await prober.run_round()
            for st in prober.state.values():
                assert st.outcome == "no_golden" and st.failures == 0
            assert _probe_count("no_golden") == ng0 + len(DEFAULT_PROBES)
            # an un-seeded store is an onboarding state, not an incident
            assert current_incident_manager().snapshot()["open"] == 0
            assert prober.model_summary()[MODEL]["outcome"] == "no_golden"
        finally:
            await _teardown(client, servers)

    asyncio.run(main())


def test_drift_drill_detects_one_noised_engine(tmp_path):
    """The acceptance drill: a 3-engine fleet with logit noise armed on
    one engine is detected within 3 probe rounds, the identity-failure
    counter ticks kind=fingerprint, exactly one canary_drift incident
    opens with bundle capture fanned to the implicated engines, and a
    clean round closes it."""
    from production_stack_tpu.router.incidents import (
        current_incident_manager,
    )
    from production_stack_tpu.testing.faults import FaultSpec

    async def main():
        engines, servers, urls = await _fleet(3)
        golden_path = str(tmp_path / "golden.json")
        await _seed_goldens(urls[0], golden_path)
        router, client, prober = await _canary_router(urls, golden_path)
        try:
            im = current_incident_manager()
            await prober.run_round()            # clean baseline round
            assert all(st.outcome == "ok" for st in prober.state.values())
            assert im.snapshot()["open"] == 0

            engines[1].fault_state.set(
                FaultSpec.parse("logit_noise_scale=0.5"))
            fp0 = _fail_count("fingerprint")
            drift0 = _probe_count("drift")

            rounds = 0
            while rounds < 3:
                await prober.run_round()
                rounds += 1
                if any(st.outcome == "drift"
                       for st in prober.state.values()):
                    break
            assert rounds <= 3, "drift not detected within 3 probe rounds"
            assert _fail_count("fingerprint") > fp0
            assert _probe_count("drift") > drift0
            # the armed noise has a guaranteed floor of 0.5 * scale
            drifted = [st for st in prober.state.values()
                       if st.outcome == "drift"]
            assert drifted and all(st.linf >= 0.25 for st in drifted)

            def open_rows():
                return [r for r in im.snapshot()["incidents"]
                        if r["status"] == "open"]

            assert im.snapshot()["open"] == 1
            (row,) = open_rows()
            inc_id = row["id"]
            assert row["trigger"] == "canary_drift"
            assert row["key"] == f"canary_drift:{MODEL}"
            assert row["window"]["kind"] == "fingerprint"
            assert row["window"]["golden_version"] == 1
            assert sorted(row["implicated"]) == sorted(urls)

            # bundle capture fans out to every implicated engine
            await _wait(
                lambda: len(open_rows()[0]["engine_bundles"]) == len(urls),
                msg="engine bundle fan-out")
            (row,) = open_rows()
            for fe, url in zip(engines, urls):
                bundle_id = row["engine_bundles"][url]
                assert not bundle_id.startswith("error"), bundle_id
                assert fe.diagnostics.bundle_path(bundle_id) is not None

            # idempotent while open: further drifting rounds re-touch. The
            # router round-robins a round's probes over the fleet, so a
            # round can miss the one noised engine (and, being clean, close
            # the incident: one run in three, by the order of the engines'
            # ports); this round drifts whichever engines it lands on
            for fe in engines:
                fe.fault_state.set(FaultSpec.parse("logit_noise_scale=0.5"))
            await prober.run_round()
            assert im.snapshot()["open"] == 1
            assert open_rows()[0]["id"] == inc_id

            # heal → a fully clean round closes the incident
            for fe in engines:
                fe.fault_state.set(None)
            await prober.run_round()
            assert all(st.outcome == "ok" for st in prober.state.values())
            assert im.snapshot()["open"] == 0
            closed = [r for r in im.snapshot()["incidents"]
                      if r["id"] == inc_id]
            assert closed and closed[0]["close_reason"] == \
                "canary probes clean"
            # stacktop's engine cell surfaces the recovery
            from tools.stacktop import _fmt_canary

            fleet_doc = await (await client.get("/debug/fleet")).json()
            assert all(_fmt_canary(r).startswith("ok")
                       for r in fleet_doc["engines"])
        finally:
            await _teardown(client, servers)

    asyncio.run(main())


def test_clean_soak_fifty_rounds_zero_false_positives(tmp_path):
    async def main():
        engines, servers, urls = await _fleet(3)
        golden_path = str(tmp_path / "golden.json")
        await _seed_goldens(urls[0], golden_path)
        router, client, prober = await _canary_router(urls, golden_path)
        try:
            from production_stack_tpu.router.incidents import (
                current_incident_manager,
            )

            ok0 = _probe_count("ok")
            drift0 = _probe_count("drift")
            err0 = _probe_count("error")
            for _ in range(50):
                await prober.run_round()
            assert prober.rounds == 50
            assert _probe_count("ok") == ok0 + 50 * len(DEFAULT_PROBES)
            assert _probe_count("drift") == drift0
            assert _probe_count("error") == err0
            assert all(st.failures == 0 for st in prober.state.values())
            assert current_incident_manager().snapshot()["open"] == 0
        finally:
            await _teardown(client, servers)

    asyncio.run(main())


def test_canary_is_observe_only_bit_identical_tenant_state(tmp_path):
    """A canary-on run leaves real tenants' usage rows and the quota
    bucket table exactly equal to a canary-off run: probes are real
    traffic on the wire (the engines see the reserved tenant) but
    invisible to metering, quotas and scale signals."""
    from production_stack_tpu.tenancy import CANARY_TENANT

    quota_cfg = json.dumps(
        {"default": {"rps": 100, "tps": 100000, "burst_s": 2, "weight": 1}})

    async def run_scenario(canary: bool):
        from aiohttp.test_utils import TestClient, TestServer

        from production_stack_tpu.router.app import RouterApp, build_parser
        from production_stack_tpu.router.canary import current_canary_prober
        from production_stack_tpu.router.slo import current_tenant_tracker

        engines, servers, urls = await _fleet(2)
        prober = None
        if canary:
            golden_path = str(tmp_path / "golden.json")
            await _seed_goldens(urls[0], golden_path)
            router, client, prober = await _canary_router(
                urls, golden_path,
                extra=("--tenant-quota-config", quota_cfg))
        else:
            args = build_parser().parse_args([
                "--service-discovery", "static",
                "--static-backends", ",".join(urls),
                "--static-models", ",".join([MODEL] * len(urls)),
                "--tenant-quota-config", quota_cfg,
            ])
            router = RouterApp(args)
            client = TestClient(TestServer(router.build_app()))
            await client.start_server()
        try:
            if prober is not None:
                for _ in range(3):
                    await prober.run_round()
            for i in range(6):
                r = await client.post(
                    "/v1/completions",
                    json={"model": MODEL, "prompt": "hi", "max_tokens": 2},
                    headers={"x-tenant-id": f"acme-{i % 2}"})
                assert r.status == 200
            if prober is not None:
                await prober.run_round()        # probes after traffic too
            tracker = current_tenant_tracker()
            rows = {t: int(v["requests"])
                    for t, v in tracker.usage_rows().items()}
            quota_keys = sorted(router.request_service.quota._buckets)
            seen = [t for fe in engines for t in fe.tenants_seen]
            return rows, quota_keys, seen
        finally:
            await _teardown(client, servers)

    async def main():
        base_rows, base_quota, base_seen = await run_scenario(canary=False)
        can_rows, can_quota, can_seen = await run_scenario(canary=True)

        assert base_rows == {"acme-0": 3, "acme-1": 3}
        # bit-identical tenant totals and quota buckets
        assert can_rows == base_rows
        assert can_quota == base_quota
        assert CANARY_TENANT not in can_rows
        assert all(CANARY_TENANT not in k for k in can_quota)
        # ... while the probes really did flow, stamped with the
        # reserved identity on every engine hop
        assert CANARY_TENANT not in base_seen
        assert CANARY_TENANT in can_seen
        assert base_seen.count("acme-0") == can_seen.count("acme-0") == 3

    asyncio.run(main())


# ---------------------------------------------------------------------------
# Operator surfaces: stacktop --canary and canaryctl
# ---------------------------------------------------------------------------

def test_stacktop_canary_cells_and_table():
    from tools.stacktop import _fmt_canary, render_canary

    assert _fmt_canary({}) == "-"
    assert _fmt_canary({"canary": {"outcome": "ok", "linf": 0.0}}) == "ok 0"
    assert _fmt_canary(
        {"canary": {"outcome": "drift", "linf": 0.25}}) == "drift 0.25"
    assert _fmt_canary({"canary": {"outcome": "no_golden"}}) == "no_golden"

    assert "--canary" in render_canary({"router": {}})

    doc = {
        "enabled": True, "interval": 30.0, "target": "http://r:8001",
        "rounds": 12, "last_round_age": 1.5,
        "golden": {"path": "golden.json",
                   "records": [{"model": MODEL, "probe": "greedy-prose",
                                "version": 3, "tolerance": 0.0,
                                "tokens": 8, "created": 0.0,
                                "source": "engine:m"}]},
        "probes": [{"model": MODEL, "probe": "greedy-prose",
                    "role_path": "disagg", "outcome": "drift",
                    "kind": "fingerprint", "detail": "d", "linf": 0.25,
                    "ttft": 0.01, "golden_version": 3, "age": 2.0,
                    "rounds": 12, "failures": 4}],
    }
    table = render_canary({"router": {"canary": doc}})
    assert "MODEL" in table and "GOLDEN" in table
    assert "greedy-prose" in table and "disagg" in table
    assert "drift" in table and "fingerprint" in table and "v3" in table
    assert "1 record(s) @ golden.json" in table
    assert "rounds 12" in table


def _serve_threaded(app_factory):
    """Run an aiohttp app on its own thread+loop so blocking stdlib
    clients (canaryctl's urllib) can call it from the test thread."""
    state = {}
    started = threading.Event()

    def run():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(app_factory())
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", 0)
        loop.run_until_complete(site.start())
        state["port"] = runner.addresses[0][1]
        state["loop"] = loop
        started.set()
        loop.run_forever()
        loop.run_until_complete(runner.cleanup())
        loop.close()

    thread = threading.Thread(target=run, daemon=True)
    thread.start()
    assert started.wait(10), "threaded server failed to start"

    def stop():
        state["loop"].call_soon_threadsafe(state["loop"].stop)
        thread.join(10)

    return state["port"], stop


def test_canaryctl_record_diff_and_drift(tmp_path):
    from production_stack_tpu.testing.fake_engine import FakeEngine
    from production_stack_tpu.testing.faults import FaultSpec
    from tools import canaryctl

    clean = FakeEngine(model=MODEL)
    drifted = FakeEngine(model=MODEL)
    drifted.fault_state.set(FaultSpec.parse("logit_noise_scale=0.5"))
    port_a, stop_a = _serve_threaded(clean.build_app)
    port_b, stop_b = _serve_threaded(drifted.build_app)
    try:
        store_a = str(tmp_path / "a.json")
        store_b = str(tmp_path / "b.json")
        engine_a = f"http://127.0.0.1:{port_a}"
        engine_b = f"http://127.0.0.1:{port_b}"

        assert canaryctl.main(
            ["record", "--engine", engine_a, "--out", store_a]) == 0
        store = GoldenStore.load(store_a)
        assert len(store.records) == len(DEFAULT_PROBES)
        assert all(r.version == 1 and r.tolerance == 0.0
                   for r in store.records.values())

        # unchanged re-record keeps versions
        assert canaryctl.main(
            ["record", "--engine", engine_a, "--out", store_a]) == 0
        assert all(r.version == 1
                   for r in GoldenStore.load(store_a).records.values())

        # a tolerance restamp is a new golden generation
        assert canaryctl.main(
            ["record", "--engine", engine_a, "--out", store_a,
             "--tolerance", "0.3"]) == 0
        assert all(r.version == 2 and r.tolerance == 0.3
                   for r in GoldenStore.load(store_a).records.values())

        # diff: identical capture → rc 0; drifted engine → rc 2
        same = str(tmp_path / "same.json")
        assert canaryctl.main(
            ["record", "--engine", engine_a, "--out", same,
             "--tolerance", "0.3"]) == 0
        assert canaryctl.main(["diff", store_a, same]) == 0
        assert canaryctl.main(
            ["record", "--engine", engine_b, "--out", store_b]) == 0
        assert canaryctl.main(["diff", store_a, store_b]) == 2

        # unreachable engine → rc 1 (OSError path)
        assert canaryctl.main(
            ["record", "--engine", "http://127.0.0.1:1",
             "--out", str(tmp_path / "x.json")]) == 1
    finally:
        stop_a()
        stop_b()

    # drift subcommand against router /debug/canary documents
    def router_stub(doc):
        def factory():
            app = web.Application()

            async def handler(request):
                return web.json_response(doc)

            app.router.add_get("/debug/canary", handler)
            return app

        return factory

    probe_row = {"model": MODEL, "probe": "greedy-prose",
                 "role_path": "unified", "outcome": "drift",
                 "kind": "fingerprint", "detail": "", "linf": 0.2,
                 "ttft": 0.01, "golden_version": 1, "age": 1.0,
                 "rounds": 3, "failures": 1}
    for doc, rc in (
        ({"enabled": False}, 1),
        ({"enabled": True, "interval": 30.0, "rounds": 3,
          "last_round_age": 1.0, "golden": {"path": "", "records": []},
          "probes": [probe_row]}, 2),
        ({"enabled": True, "interval": 30.0, "rounds": 3,
          "last_round_age": 1.0, "golden": {"path": "", "records": []},
          "probes": [dict(probe_row, outcome="ok", kind="",
                          failures=0)]}, 0),
    ):
        port, stop = _serve_threaded(router_stub(doc))
        try:
            assert canaryctl.main(
                ["drift", "--router", f"http://127.0.0.1:{port}"]) == rc
        finally:
            stop()
