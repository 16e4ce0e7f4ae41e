"""Goodput accounting + SLO engine: PerfAccountant arithmetic against
the docs/roofline.md formulas, compile-event tracking over a real (tiny)
engine, the router's burn-rate tracker against the alert rules evaluated
offline, and the satellite fixes (percentile off-by-one, scraper
lifecycle, profiler endpoint error paths)."""

import argparse
import asyncio
import re
from pathlib import Path

import numpy as np
import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    PerfConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.perf_accounting import (
    CompileTracker,
    PerfAccountant,
    estimate_param_count,
    wrap_runner_programs,
)
from production_stack_tpu.engine.server import EngineServer
from production_stack_tpu.parallel.mesh import MeshConfig
from production_stack_tpu.router.slo import (
    PAGE_BURN,
    WARN_BURN,
    SLOConfig,
    SLOTracker,
    current_slo_tracker,
    initialize_slo_tracker,
)
from production_stack_tpu.router.stats import (
    EngineStatsScraper,
    MovingAverageMonitor,
    RequestStatsMonitor,
)

REPO = Path(__file__).resolve().parent.parent


def tiny_cfg() -> ModelConfig:
    return ModelConfig(
        vocab_size=64, hidden_size=8, intermediate_size=16, num_layers=2,
        num_heads=2, num_kv_heads=1, head_dim=4, dtype="bfloat16",
    )


# -- PerfAccountant arithmetic (docs/roofline.md, live) ----------------------

def make_accountant(**kw) -> PerfAccountant:
    kw.setdefault("param_count", 1000)
    kw.setdefault("param_bytes", 2000)
    kw.setdefault("window", 60.0)
    # 1e6 FLOP/s and 1e6 B/s peaks make utilizations readable fractions
    kw.setdefault("peak_tflops", 1e-6)
    kw.setdefault("peak_hbm_gbps", 1e-3)
    return PerfAccountant(tiny_cfg(), **kw)


def test_perf_accountant_prefill_decode_arithmetic():
    acc = make_accountant()
    # attn flops/token/ctx = 4*L*H*D = 4*2*2*4 = 64
    # kv bytes/token       = 2*L*KH*D*2 = 2*2*1*4*2 = 32
    acc.record_ragged(10, 30, 2, 0, 0, ts=100.0)
    acc.record_decode(live_seqs=4, steps=2, ctx_tokens=40, ts=101.0)
    rates = acc._window_rates(101.0)  # span = 1s
    prefill_flops = 2 * 1000 * 10 + 64 * 10 * 15      # ctx_mean = 30/2
    decode_flops = 2 * 1000 * 8 + 64 * 40 * 2         # tokens = 4*2
    prefill_hbm = 2000 + (10 + 30) * 32
    decode_hbm = 2 * (2000 + (40 + 4) * 32)
    assert rates["mfu"] == pytest.approx(
        (prefill_flops + decode_flops) / 1e6)
    assert rates["hbm_bw_util"] == pytest.approx(
        (prefill_hbm + decode_hbm) / 1e6)
    assert rates["prefill_tps"] == pytest.approx(10.0)
    assert rates["decode_tps"] == pytest.approx(8.0)


def test_perf_accountant_window_trim_keeps_totals():
    acc = make_accountant(window=60.0)
    acc.record_ragged(10, 10, 1, 0, 0, ts=100.0)
    acc.record_decode(live_seqs=1, steps=1, ctx_tokens=4, ts=200.0)
    rates = acc._window_rates(200.0)
    assert len(acc._events) == 1  # the ts=100 prefill fell out
    assert rates["prefill_tps"] == 0.0
    assert rates["decode_tps"] > 0.0
    # cumulative totals survive the sliding window
    assert acc._totals["prefill_tokens"] == 10
    assert acc._totals["dispatches"] == 2


def test_perf_accountant_empty_window_rates_are_zero():
    acc = make_accountant()
    rates = acc._window_rates(0.0)
    assert rates["mfu"] == 0.0 and rates["hbm_bw_util"] == 0.0
    assert rates["prefill_tps"] == 0.0 and rates["decode_tps"] == 0.0
    # no ICI peak was given: that axis has no utilization, not 0%
    assert rates["ici_bw_util"] is None


def test_compile_events_and_steady_state_marking():
    acc = make_accountant()
    acc.on_compile("prefill", "4x32", 1.5)
    acc.on_compile("decode", "4", 0.5)
    snap = acc.snapshot()
    assert snap["compile"]["total_events"] == 2
    assert snap["compile"]["total_seconds"] == pytest.approx(2.0)
    assert snap["compile"]["unexpected_recompiles"] == 0
    assert snap["compile"]["counts"] == {"prefill:4x32": 1, "decode:4": 1}
    # after warmup, any fresh compile is a leak — the alert-rule signal
    acc.mark_steady()
    acc.on_compile("prefill", "4x64", 2.0)
    fields = acc.stats_fields()
    assert fields["unexpected_recompiles"] == 1
    assert fields["compile_seconds_total"] == pytest.approx(4.0)
    assert acc.snapshot()["compile"]["recent"][-1]["unexpected"] is True


def test_estimate_param_count_matches_geometry():
    # qkv+o = 64+64+64, mlp = 3*8*16 = 384, embed+lm_head = 2*64*8
    assert estimate_param_count(tiny_cfg()) == 2 * 64 * 8 + 2 * (192 + 384)


# -- CompileTracker: signature dedup ----------------------------------------

def test_compile_tracker_counts_new_signatures_only():
    events = []
    tracker = CompileTracker("prefill", lambda *a, **k: 42,
                             lambda k, b, s, build: events.append((k, b)))
    a28 = np.zeros((2, 8), np.int32)
    assert tracker(None, None, a28) == 42
    assert events == [("prefill", "2x8")]
    tracker(None, None, np.ones((2, 8), np.int32))  # same shapes: cached
    assert len(events) == 1
    tracker(None, None, np.zeros((2, 16), np.int32))  # new bucket
    assert events[-1] == ("prefill", "2x16")
    tracker(None, None, a28, flag=True)  # static kwarg → new executable
    assert len(events) == 3
    # dtype is part of the signature too
    tracker(None, None, a28.astype(np.int64))
    assert len(events) == 4


def test_wrap_runner_programs_is_idempotent():
    class Runner:
        def __init__(self):
            self._ragged = lambda *a: "p"
            self._decode_multi = None  # absent variants are skipped

    runner = Runner()
    wrap_runner_programs(runner, lambda *a: None)
    wrap_runner_programs(runner, lambda *a: None)
    assert isinstance(runner._ragged, CompileTracker)
    assert not isinstance(runner._ragged.fn, CompileTracker)
    assert runner._decode_multi is None


# -- engine integration: /debug/perf + gauges over a real tiny engine --------

def make_server() -> EngineServer:
    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=512),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_num_batched_tokens=64,
            ),
        mesh=MeshConfig(data=1, tensor=1),
        # the CPU has no entry in DEVICE_PEAKS: utilization needs peaks
        perf=PerfConfig(peak_tflops=1e-3, peak_hbm_gbps=1.0),
    )
    return EngineServer(cfg)


@pytest.fixture(scope="module")
def server():
    return make_server()


async def _with_client(server, fn):
    from aiohttp.test_utils import TestClient, TestServer

    async with TestClient(TestServer(server.build_app())) as client:
        return await fn(client)


def _metric_value(text: str, prefix: str) -> float:
    total = 0.0
    for line in text.splitlines():
        if line.startswith(prefix):
            total += float(line.rsplit(" ", 1)[1])
    return total


def test_debug_perf_and_metrics_after_traffic(server):
    async def fn(client):
        r = await client.post(
            "/v1/completions",
            json={"model": "tiny-llama", "prompt": "hello",
                  "max_tokens": 4, "temperature": 0, "ignore_eos": True},
        )
        assert r.status == 200

        r = await client.get("/debug/perf")
        perf = await r.json()
        assert perf["enabled"] is True
        assert perf["model"]["param_count"] > 0
        # goodput gauges are live after one prefill+decode round
        assert perf["model_flops_utilization"] > 0
        assert perf["hbm_bandwidth_utilization"] > 0
        assert perf["tokens_per_second"]["prefill"] > 0
        assert perf["tokens_per_second"]["decode"] > 0
        assert perf["totals"]["dispatches"] >= 2
        # the first request compiled at least the prefill + decode progs
        assert perf["compile"]["total_events"] >= 1
        assert perf["compile"]["total_seconds"] > 0
        assert perf["compile"]["unexpected_recompiles"] == 0
        assert perf["compile"]["recent"], "event tail empty"

        r = await client.get("/metrics")
        text = await r.text()
        assert _metric_value(text, "vllm:model_flops_utilization") > 0
        assert _metric_value(text, "vllm:hbm_bandwidth_utilization") > 0
        assert _metric_value(text, "vllm:tokens_per_second") > 0
        assert _metric_value(text, "vllm:compile_events_total") >= 1
        assert _metric_value(text, "vllm:compile_time_seconds_total") > 0
        assert "vllm:unexpected_recompiles_total" in text
        assert "vllm:hbm_bytes_used" in text  # 0 on CPU, but exported

    asyncio.run(_with_client(server, fn))


def test_unknown_device_kind_exports_no_utilization(server):
    """No peaks for the device (the CPU is not in DEVICE_PEAKS) and none
    given: token rates and compile events are reported, utilization is
    not — a v5e-relative "MFU" of a CPU run is not a utilization."""
    import dataclasses

    from production_stack_tpu.engine.perf_accounting import DEVICE_PEAKS

    assert "cpu" not in DEVICE_PEAKS
    cfg = dataclasses.replace(server.config, perf=PerfConfig())
    acct = PerfAccountant.from_runner(cfg, server.engine.runner)
    acct.record_decode(live_seqs=2, steps=1, ctx_tokens=8)
    kept, server.engine.perf = server.engine.perf, acct

    async def fn(client):
        perf = await (await client.get("/debug/perf")).json()
        assert perf["model_flops_utilization"] is None
        assert perf["hbm_bandwidth_utilization"] is None
        assert perf["rooflines"]["flop"]["peak_per_s"] is None
        assert perf["rooflines"]["flop"]["achieved_per_s"] > 0
        assert perf["tokens_per_second"]["decode"] > 0
        text = await (await client.get("/metrics")).text()
        assert "vllm:model_flops_utilization" not in text
        assert "vllm:hbm_bandwidth_utilization" not in text
        assert "vllm:ici_bandwidth_utilization" not in text
        assert _metric_value(text, "vllm:tokens_per_second") > 0

    try:
        asyncio.run(_with_client(server, fn))
    finally:
        server.engine.perf = kept


def test_unexpected_recompile_after_steady(server):
    async def fn(client):
        # warm the plain programs, declare steady, then send the first
        # request of a static variant nothing warmed (a logit bias: the
        # use_controls programs). The ragged step compiles for no prompt
        # length, so a variant is what is left to leak a compile
        r = await client.post(
            "/v1/completions",
            json={"model": "tiny-llama", "prompt": "warm",
                  "max_tokens": 2, "temperature": 0, "ignore_eos": True},
        )
        assert r.status == 200
        server.engine.perf.mark_steady()
        before = server.engine.perf.stats_fields()["unexpected_recompiles"]
        r = await client.post(
            "/v1/completions",
            json={"model": "tiny-llama", "prompt": "x" * 50,
                  "max_tokens": 2, "temperature": 0, "ignore_eos": True,
                  "logit_bias": {"7": 0.5}},
        )
        assert r.status == 200
        after = server.engine.perf.stats_fields()["unexpected_recompiles"]
        assert after > before

        r = await client.get("/debug/perf")
        assert (await r.json())["compile"]["steady"] is True

    asyncio.run(_with_client(server, fn))


# -- profiler endpoints (satellite: error paths never leak a running
#    profiler) ---------------------------------------------------------------

def test_profile_roundtrip_and_memory_profile(server, monkeypatch):
    import jax

    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda path, **options: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(jax.profiler, "device_memory_profile",
                        lambda: b"pprof-bytes")

    async def fn(client):
        r = await client.post("/debug/profile", json={"duration_ms": 10})
        assert r.status == 200
        assert r.content_type == "application/gzip"
        assert (await r.read())[:2] == b"\x1f\x8b"  # gzip magic
        assert server._profiling is False

        r = await client.get("/debug/memory")
        assert r.status == 200
        assert await r.read() == b"pprof-bytes"

    asyncio.run(_with_client(server, fn))


def test_profile_409_while_capture_running(server):
    async def fn(client):
        server._profiling = True
        try:
            r = await client.post("/debug/profile", json={})
            assert r.status == 409
            assert "already running" in (await r.json())["error"]["message"]
        finally:
            server._profiling = False

    asyncio.run(_with_client(server, fn))


def test_profile_start_failure_is_500_and_resets(server, monkeypatch):
    import jax

    def boom(path, **options):
        raise RuntimeError("no backend profiler")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)

    async def fn(client):
        r = await client.post("/debug/profile", json={"duration_ms": 10})
        assert r.status == 500
        assert "profile capture failed" in (await r.json())["error"]["message"]
        assert server._profiling is False

    asyncio.run(_with_client(server, fn))


def test_profile_stop_failure_is_500_and_resets(server, monkeypatch):
    import jax

    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda path, **options: None)

    def boom():
        raise RuntimeError("serialization failed")

    monkeypatch.setattr(jax.profiler, "stop_trace", boom)

    async def fn(client):
        r = await client.post("/debug/profile", json={"duration_ms": 10})
        assert r.status == 500
        # the finally-block retry swallowed the second stop failure and
        # the endpoint stays usable
        assert server._profiling is False

    asyncio.run(_with_client(server, fn))


def test_memory_profile_failure_is_json_500(server, monkeypatch):
    import jax

    def boom():
        raise RuntimeError("unsupported")

    monkeypatch.setattr(jax.profiler, "device_memory_profile", boom)

    async def fn(client):
        r = await client.get("/debug/memory")
        assert r.status == 500
        assert "memory profile failed" in (await r.json())["error"]["message"]

    asyncio.run(_with_client(server, fn))


# -- percentile off-by-one (satellite fix) -----------------------------------

def test_percentile_nearest_rank_small_windows():
    mon = MovingAverageMonitor(window=1e9)
    assert mon.percentile(0.95) == -1.0  # empty window
    for v in range(1, 21):
        mon.update(float(v), float(v))
    # nearest rank ceil(0.95*20)=19 → value 19; int(0.95*20)=19 indexed
    # the MAX (20) before the fix
    assert mon.percentile(0.95) == 19.0
    assert mon.percentile(0.5) == 10.0
    assert mon.percentile(1.0) == 20.0
    assert mon.percentile(0.0) == 1.0  # clamped to the first rank

    single = MovingAverageMonitor(window=1e9)
    single.update(0.0, 7.0)
    assert single.percentile(0.99) == 7.0


# -- scraper lifecycle (satellite fix) ---------------------------------------

def test_scraper_start_is_idempotent_and_stop_is_cancel_safe():
    async def main():
        s = EngineStatsScraper(interval=3600.0)
        await s.stop()  # stop before any start: no-op
        assert s.get_health() is False
        await s.start()
        task = s._task
        await s.start()  # second start must not replace/leak the worker
        assert s._task is task
        assert s.get_health() is True
        # stop before the worker ever got scheduled: cancellation still
        # lands and nothing outlives stop()
        await s.stop()
        assert s.get_health() is False
        assert task.cancelled()
        await s.stop()  # idempotent

        # restartable after stop
        await s.start()
        assert s.get_health() is True
        await s.stop()

    asyncio.run(main())


# -- SLO tracker units -------------------------------------------------------

T0 = 1_000_000.0  # bin-aligned epoch for deterministic tests


def slo_config(**kw) -> SLOConfig:
    kw.setdefault("ttft_p95", 0.5)
    kw.setdefault("tail_budget", 0.05)
    return SLOConfig(**kw)


def test_slo_objectives_and_per_model_overrides():
    cfg = slo_config(availability=0.99,
                     per_model={"big": {"ttft_p95": 2.0}})
    assert cfg.objectives("any") == {
        "ttft_p95": (0.5, 0.05), "availability": (0.99, pytest.approx(0.01)),
    }
    assert cfg.objectives("big")["ttft_p95"] == (2.0, 0.05)
    # a 0 objective is off entirely
    assert "itl_p95" not in cfg.objectives("any")


def test_slo_config_from_args_none_when_unconfigured():
    ns = argparse.Namespace(slo_ttft_p95=0.0, slo_itl_p95=0.0,
                            slo_availability=0.0, slo_tail_budget=0.05,
                            slo_config=None)
    assert SLOConfig.from_args(ns) is None
    ns.slo_config = '{"m": {"ttft_p95": 1.0}}'
    cfg = SLOConfig.from_args(ns)
    assert cfg is not None and cfg.per_model["m"]["ttft_p95"] == 1.0


def test_slo_burn_rates_and_budget():
    tracker = SLOTracker(slo_config())
    # 19 good + 1 bad = 5% bad → burn exactly 1.0 (budget spent on pace)
    for i in range(19):
        tracker.record_ttft("m", 0.1, ts=T0 + i)
    tracker.record_ttft("m", 9.9, ts=T0 + 19)
    rates = tracker.burn_rates("m", "ttft_p95", now=T0 + 20)
    assert rates["5m"] == pytest.approx(1.0)
    assert rates["6h"] == pytest.approx(1.0)
    assert tracker.error_budget_remaining(
        "m", "ttft_p95", now=T0 + 20) == pytest.approx(0.0)
    # all-bad burns at 1/budget = 20
    hot = SLOTracker(slo_config())
    for i in range(10):
        hot.record_ttft("m", 9.9, ts=T0 + i)
    assert hot.burn_rates("m", "ttft_p95",
                          now=T0 + 10)["5m"] == pytest.approx(20.0)
    assert hot.error_budget_remaining(
        "m", "ttft_p95", now=T0 + 10) == pytest.approx(-19.0)


def test_slo_windows_age_out():
    tracker = SLOTracker(slo_config())
    tracker.record_ttft("m", 9.9, ts=T0)
    # fully bad inside 5m; gone from the 5m window half an hour later
    assert tracker.burn_rates("m", "ttft_p95", now=T0 + 60)["5m"] > 0
    later = tracker.burn_rates("m", "ttft_p95", now=T0 + 1800)
    assert later["5m"] == 0.0
    assert later["6h"] > 0  # still inside the long window


def test_slo_unconfigured_model_records_nothing():
    tracker = SLOTracker(SLOConfig(availability=0.999))
    tracker.record_ttft("m", 99.0, ts=T0)  # no ttft objective → dropped
    assert tracker._series == {}
    tracker.record_attempt("m", False, ts=T0)
    assert ("m", "availability") in tracker._series


def test_slo_snapshot_shape():
    tracker = SLOTracker(slo_config())
    tracker.record_ttft("m", 9.9, ts=T0)
    snap = tracker.snapshot(now=T0 + 30)
    assert snap["thresholds"] == {
        "page_burn": PAGE_BURN, "warn_burn": WARN_BURN,
        "fast_windows": ["5m", "1h"], "slow_windows": ["30m", "6h"],
    }
    (row,) = snap["series"]
    assert row["model"] == "m" and row["slo"] == "ttft_p95"
    assert row["objective"] == 0.5
    assert set(row["burn_rate"]) == {"5m", "30m", "1h", "6h"}
    assert "page" in row and "warn" in row


# -- acceptance: the tracker pages exactly when the alert rule fires ---------

def test_burn_rate_pages_exactly_when_alert_rule_fires():
    """Evaluate observability/alert-rules.yaml's SLOFastBurnPage offline
    against a synthetic TTFT-violation ramp: the tracker's page flag must
    flip at the same step the rule expression crosses its thresholds."""
    text = (REPO / "observability" / "alert-rules.yaml").read_text()
    block = text[text.index("SLOFastBurnPage"):]
    block = block[:block.index("- alert:", 1)]
    thresholds = dict(re.findall(
        r'vllm:slo_burn_rate\{window="(5m|1h)"\}\)\s*>\s*([0-9.]+)', block))
    assert set(thresholds) == {"5m", "1h"}, block
    # the YAML must carry the same numbers the tracker pages on
    assert float(thresholds["5m"]) == PAGE_BURN
    assert float(thresholds["1h"]) == PAGE_BURN

    tracker = SLOTracker(slo_config(ttft_p95=0.1))
    # an hour of healthy traffic seeds the 1h window
    for i in range(10):
        tracker.record_ttft("m", 0.01, ts=T0 - 3600 + i * 300)

    fired_at = None
    for step in range(40):
        now = T0 + step * 30
        for _ in range(5):
            tracker.record_ttft("m", 5.0, ts=now)  # hard violation
        rates = tracker.burn_rates("m", "ttft_p95", now=now)
        rule_fires = (rates["5m"] > float(thresholds["5m"])
                      and rates["1h"] > float(thresholds["1h"]))
        page = tracker._flags(rates)["page"]
        assert page == rule_fires, f"step {step}: {rates}"
        if rule_fires and fired_at is None:
            fired_at = step
    # the healthy hour keeps the first violations from paging instantly
    # (that's the multi-window point), but a sustained storm must page
    assert fired_at is not None and fired_at > 0


# -- stats monitor → SLO feed, and the router surfaces -----------------------

def test_request_stats_monitor_feeds_slo_tracker():
    tracker = initialize_slo_tracker(
        SLOConfig(ttft_p95=0.2, itl_p95=0.05, availability=0.99))
    try:
        mon = RequestStatsMonitor(sliding_window=60.0)
        url = "http://e1"
        # request 1: slow first token (bad ttft), slow itl, but completes
        mon.on_new_request(url, "r1", T0, model="m")
        mon.on_request_response(url, "r1", T0 + 1.0)
        mon.on_request_complete(url, "r1", T0 + 2.0, num_output_tokens=5)
        # request 2: never produced a first byte → availability violation
        mon.on_new_request(url, "r2", T0 + 3.0, model="m")
        mon.on_request_complete(url, "r2", T0 + 4.0, num_output_tokens=0)

        now = T0 + 5.0
        assert tracker.burn_rates("m", "ttft_p95", now=now)["5m"] > 0
        assert tracker.burn_rates("m", "itl_p95", now=now)["5m"] > 0
        assert tracker.burn_rates("m", "availability", now=now)["5m"] > 0
        assert mon.request_model == {}  # attribution map drains
    finally:
        initialize_slo_tracker(None)


def test_router_debug_slo_and_burn_gauges():
    async def main():
        from aiohttp.test_utils import TestClient, TestServer

        from production_stack_tpu.router.app import RouterApp, build_parser

        args = build_parser().parse_args([
            "--service-discovery", "static",
            "--static-backends", "http://127.0.0.1:1",
            "--static-models", "tiny-llama",
            "--slo-ttft-p95", "0.2",
            "--slo-availability", "0.99",
        ])
        router = RouterApp(args)
        client = TestClient(TestServer(router.build_app()))
        await client.start_server()
        try:
            tracker = current_slo_tracker()
            assert tracker is not None
            tracker.record_ttft("tiny-llama", 5.0)  # violation right now
            r = await client.get("/debug/slo")
            data = await r.json()
            assert data["enabled"] is True
            assert data["config"]["ttft_p95"] == 0.2
            row = next(s for s in data["series"]
                       if s["slo"] == "ttft_p95")
            assert row["model"] == "tiny-llama"
            assert row["burn_rate"]["5m"] > 0

            r = await client.get("/metrics")
            text = await r.text()
            assert 'vllm:slo_burn_rate{' in text
            assert 'vllm:slo_error_budget_remaining{' in text
            assert _metric_value(
                text, 'vllm:slo_burn_rate{model="tiny-llama",'
                'slo="ttft_p95",window="5m"}') > 0
        finally:
            await client.close()

    try:
        asyncio.run(main())
    finally:
        initialize_slo_tracker(None)


def test_router_debug_slo_disabled_without_objectives():
    async def main():
        from aiohttp.test_utils import TestClient, TestServer

        from production_stack_tpu.router.app import RouterApp, build_parser

        args = build_parser().parse_args([
            "--service-discovery", "static",
            "--static-backends", "http://127.0.0.1:1",
            "--static-models", "tiny-llama",
        ])
        router = RouterApp(args)
        client = TestClient(TestServer(router.build_app()))
        await client.start_server()
        try:
            assert current_slo_tracker() is None
            r = await client.get("/debug/slo")
            assert (await r.json())["enabled"] is False
            r = await client.get("/metrics")  # refresh path tolerates None
            assert r.status == 200
        finally:
            await client.close()

    try:
        asyncio.run(main())
    finally:
        initialize_slo_tracker(None)
