"""``GET /debug/fleet``: one snapshot of the whole serving fleet.

Joins, per discovered engine, what today lives behind N different
endpoints — the stats scraper's queue/KV view, the request monitor's
QPS/TTFT view, discovery's ready/warming/draining classification, and a
live ``/debug/perf`` + ``/ready`` probe for MFU / HBM / watchdog state —
with the router's own SLO, scale-advisor and incident views.  This is
the data plane behind ``tools/stacktop.py`` (one-shot and ``--watch``
rendering, nvidia-smi-style for the fleet).

The per-engine probes run concurrently with a short timeout; an engine
that doesn't answer still gets a row (status "unreachable") — a fleet
view that drops sick engines is useless exactly when it matters.
"""

from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Optional

PROBE_TIMEOUT = 2.0


async def _probe_engine(session, url: str) -> dict:
    """Fetch /debug/perf + /ready concurrently; either may fail alone."""
    import aiohttp

    timeout = aiohttp.ClientTimeout(total=PROBE_TIMEOUT)

    async def get_json(path: str) -> Optional[dict]:
        try:
            async with session.get(f"{url}{path}", timeout=timeout) as resp:
                return await resp.json()
        except Exception:
            return None

    perf, ready = await asyncio.gather(get_json("/debug/perf"),
                                       get_json("/ready"))
    return {"perf": perf, "ready": ready}


def _canary_cell(ep, canary_by_model: dict) -> Optional[dict]:
    """Worst canary verdict across the models this engine serves — the
    prober probes per (model, role-path), so the join key is the model
    name, not the engine URL."""
    if not canary_by_model:
        return None
    rank = {"": 0, "ok": 1, "no_golden": 2, "error": 3, "drift": 4}
    worst = None
    for model in ep.model_names:
        row = canary_by_model.get(model)
        if row is None:
            continue
        if worst is None or (rank.get(row.get("outcome", ""), 0)
                             > rank.get(worst.get("outcome", ""), 0)):
            worst = row
    return worst


def _engine_row(ep, probe: dict, estats, rstats, reasons: dict,
                incidents, canary_by_model: Optional[dict] = None) -> dict:
    perf = probe.get("perf") or {}
    ready = probe.get("ready")
    hbm = perf.get("hbm_bytes") or {}
    tps = perf.get("tokens_per_second") or {}
    compile_info = perf.get("compile") or {}
    if ready is not None:
        status = ready.get("status", "ready")
        if status == "healthy":
            status = "ready"
    elif ep.draining:
        status = "draining"
    elif ep.sleep:
        status = "sleeping"
    else:
        status = reasons.get(ep.url) or "unreachable"
    kv_usage = estats.gpu_cache_usage_perc if estats else None
    return {
        "url": ep.url,
        "models": list(ep.model_names),
        "label": ep.model_label,
        "role": ep.role,
        "kv_transfer": perf.get("kv_transfer"),
        # tiered-KV snapshot (tiers/bytes/prefetch) from /debug/perf —
        # None for engines without host/remote tiers configured
        "kv_tier": perf.get("kv_tier"),
        # per-tenant attribution block (tokens/chip-seconds/KV, folded to
        # top-K + "other") — None for engines with metering off
        "tenants": perf.get("tenants"),
        "status": status,
        "draining": ep.draining,
        "warming": status == "warming",
        "watchdog_stalled": status == "stalled",
        "mfu": perf.get("model_flops_utilization"),
        "ici": perf.get("ici_bandwidth_utilization"),
        "chips": perf.get("chips"),
        "hbm_used_bytes": hbm.get("used"),
        "hbm_total_bytes": hbm.get("total"),
        "kv_usage": kv_usage,
        "kv_free": (1.0 - kv_usage) if kv_usage is not None else None,
        "waiting": estats.num_queuing_requests if estats else None,
        "running": estats.num_running_requests if estats else None,
        "qps": rstats.qps if rstats else None,
        "ttft": rstats.ttft if rstats else None,
        "tokens_per_second": tps or None,
        "unexpected_recompiles": compile_info.get("unexpected_recompiles"),
        # correctness-canary verdict for this engine's model(s): last
        # outcome + max logit error from the router's prober — None
        # when the canary plane is off or hasn't probed yet
        "canary": _canary_cell(ep, canary_by_model or {}),
        "incidents": (incidents.open_incidents_for(ep.url)
                      if incidents is not None else []),
    }


async def fleet_snapshot(session) -> dict:
    """The /debug/fleet document. ``session`` is the router's shared
    backend ClientSession (request_service.session)."""
    from production_stack_tpu.router.incidents import (
        current_incident_manager,
    )
    from production_stack_tpu.router.scale_advisor import (
        current_scale_advisor,
    )
    from production_stack_tpu.router.service_discovery import (
        get_service_discovery,
    )
    from production_stack_tpu.router.slo import current_slo_tracker
    from production_stack_tpu.router.stats import (
        get_engine_stats_scraper,
        get_request_stats_monitor,
    )

    discovery = get_service_discovery()
    endpoints = discovery.get_endpoint_info()
    reasons = dict(getattr(discovery, "not_ready_reason", {}))
    try:
        engine_stats = get_engine_stats_scraper().get_engine_stats()
    except AssertionError:
        engine_stats = {}
    try:
        request_stats = get_request_stats_monitor().get_request_stats()
    except AssertionError:
        request_stats = {}
    incidents = current_incident_manager()
    from production_stack_tpu.router.canary import current_canary_prober

    prober = current_canary_prober()
    canary_by_model = prober.model_summary() if prober is not None else {}
    probes = await asyncio.gather(
        *(_probe_engine(session, ep.url) for ep in endpoints))
    engines = [
        _engine_row(ep, probe, engine_stats.get(ep.url),
                    request_stats.get(ep.url), reasons, incidents,
                    canary_by_model)
        for ep, probe in zip(endpoints, probes)
    ]
    tracker = current_slo_tracker()
    advisor = current_scale_advisor()
    from production_stack_tpu.router import metrics as m
    from production_stack_tpu.router.slo import current_tenant_tracker

    tenant_tracker = current_tenant_tracker()
    return {
        "ts": time.time(),
        "engines": engines,
        "router": {
            "slo": tracker.snapshot() if tracker is not None else None,
            "tenants": (tenant_tracker.snapshot()
                        if tenant_tracker is not None else None),
            "scale": advisor.snapshot() if advisor is not None else None,
            "incidents": (incidents.snapshot() if incidents is not None
                          else {"open": 0, "incidents": []}),
            "canary": (prober.snapshot() if prober is not None
                       else {"enabled": False}),
            "disagg": m.disagg_snapshot(),
        },
    }


async def engine_tenants(session) -> dict:
    """Per-engine GET /debug/tenants probe for the router's joined
    /debug/tenants view — same concurrent short-timeout shape as the
    fleet probes; an engine that doesn't answer gets None."""
    import aiohttp

    from production_stack_tpu.router.service_discovery import (
        get_service_discovery,
    )

    timeout = aiohttp.ClientTimeout(total=PROBE_TIMEOUT)

    async def probe(url: str):
        try:
            async with session.get(f"{url}/debug/tenants",
                                   timeout=timeout) as resp:
                return await resp.json()
        except Exception:
            return None

    endpoints = get_service_discovery().get_endpoint_info()
    results = await asyncio.gather(*(probe(ep.url) for ep in endpoints))
    return {ep.url: res for ep, res in zip(endpoints, results)}


def request_stats_asdict(stats) -> dict:
    return dataclasses.asdict(stats)
