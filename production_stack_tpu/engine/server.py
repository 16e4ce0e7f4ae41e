"""OpenAI-compatible engine server (aiohttp).

The TPU-native stand-in for a vLLM engine pod: serves the OpenAI surface the
reference router proxies to (reference endpoint list:
src/vllm_router/routers/main_router.py:51-301) and the ``/metrics`` +
``/v1/models`` + ``/health`` + sleep-family contract the router's service
discovery and stats scraper depend on
(src/vllm_router/service_discovery.py:504-623).

Endpoints: /v1/completions, /v1/chat/completions (SSE streaming), /v1/models,
/health, /version, /tokenize, /detokenize, /metrics, /sleep, /wake_up,
/is_sleeping.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import time
import uuid
from typing import Optional

from aiohttp import web
from prometheus_client import generate_latest, CONTENT_TYPE_LATEST

from production_stack_tpu import __version__
from production_stack_tpu.engine.async_engine import AsyncEngine
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.diagnostics import (
    DiagnosticsConfig,
    DiagnosticsManager,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.lifecycle import StepWatchdog
from production_stack_tpu.engine.metrics import ServerMetrics
from production_stack_tpu.engine.overload import (
    BrownoutController,
    PressureSignals,
    SHED_MAX_TOKENS,
    SHED_PREFETCH,
    SHED_SPEC,
    SHED_TENANT,
    overweight_tenants,
)
from production_stack_tpu.engine import tracing as etracing
from production_stack_tpu.flight_recorder import FlightRecorder
from production_stack_tpu.tenancy import resolve_tenant

import logging

_log = logging.getLogger("engine.server")
from production_stack_tpu.engine.sampling import (
    SamplingParams,
    make_token_controls,
)


def _log_bg_task_failure(task: "asyncio.Task") -> None:
    """Done-callback for fire-and-forget tasks: surface the exception a
    dropped task would report only at GC time, if ever."""
    if task.cancelled():
        return
    exc = task.exception()
    if exc is not None:
        _log.warning("background task failed", exc_info=exc)


def _sampling_from_body(body: dict) -> SamplingParams:
    stop = body.get("stop") or ()
    if isinstance(stop, str):
        stop = (stop,)
    seed = body.get("seed")
    if seed is not None:
        seed = int(seed) & 0xFFFFFFFF  # device seeds are uint32
    n = body.get("n")
    return SamplingParams(
        max_tokens=int(body.get("max_tokens") or 16),
        temperature=float(body.get("temperature", 1.0)),
        top_p=float(body.get("top_p", 1.0)),
        top_k=int(body.get("top_k", -1)),
        seed=seed,
        stop=tuple(stop),
        stop_token_ids=tuple(body.get("stop_token_ids") or ()),
        ignore_eos=bool(body.get("ignore_eos", False)),
        n=int(n) if n is not None else 1,  # n=0 must reach the validator
        presence_penalty=float(body.get("presence_penalty", 0.0)),
        frequency_penalty=float(body.get("frequency_penalty", 0.0)),
        # OpenAI logit_bias carries string token-id keys; vLLM's
        # allowed_token_ids restricts sampling to a whitelist
        logit_bias=_parse_logit_bias(body.get("logit_bias")),
        allowed_token_ids=tuple(body.get("allowed_token_ids") or ()),
    )


def _parse_logprobs(body: dict, chat: bool) -> Optional[int]:
    """OpenAI logprobs knobs → the engine's single count.

    chat: ``logprobs: true`` (+ ``top_logprobs: 0..20``); completions:
    ``logprobs: <int 0..20>`` (OpenAI caps 5; we allow the device limit).
    Returns the top-N count, or None when logprobs weren't requested."""
    from production_stack_tpu.engine.sampling import MAX_LOGPROBS

    if chat:
        if not body.get("logprobs"):
            return None
        top = body.get("top_logprobs")
        top = int(top) if top is not None else 0
        if not 0 <= top <= MAX_LOGPROBS:
            raise ValueError(f"top_logprobs must be in [0, {MAX_LOGPROBS}]")
        return top
    raw = body.get("logprobs")
    if raw is None or raw is False:
        return None
    if raw is True:
        raise ValueError(
            "completions logprobs must be an integer count; the boolean "
            "form belongs to /v1/chat/completions"
        )
    n = int(raw)
    if not 0 <= n <= MAX_LOGPROBS:
        raise ValueError(f"logprobs must be in [0, {MAX_LOGPROBS}]")
    return n


def _fmt_chat_logprobs(tk, token_ids: list, lps: list, n_top: int) -> dict:
    """OpenAI chat logprobs shape for one span of tokens."""
    content = []
    for t, (lp, top) in zip(token_ids, lps):
        s = tk.decode([t])
        content.append({
            "token": s, "logprob": lp, "bytes": list(s.encode()),
            "top_logprobs": [
                {"token": tk.decode([tid]), "logprob": v,
                 "bytes": list(tk.decode([tid]).encode())}
                for tid, v in top[:n_top]
            ],
        })
    return {"content": content}


def _fmt_completion_logprobs(tk, token_ids: list, lps: list, n_top: int,
                             offset0: int = 0) -> dict:
    """OpenAI completions logprobs shape (tokens / token_logprobs /
    top_logprobs / text_offset)."""
    tokens, tlps, tops, offsets = [], [], [], []
    off = offset0
    for t, (lp, top) in zip(token_ids, lps):
        s = tk.decode([t])
        tokens.append(s)
        tlps.append(lp)
        offsets.append(off)
        off += len(s)
        if n_top and top:  # first echoed token has no prediction (None, [])
            # dict keyed by token string (OpenAI shape): distinct ids can
            # decode to the same string — the highest-ranked keeps the key
            d: dict = {}
            for tid, v in top[:n_top]:
                d.setdefault(tk.decode([tid]), v)
            tops.append(d)
        else:
            tops.append(None)
    return {"tokens": tokens, "token_logprobs": tlps, "top_logprobs": tops,
            "text_offset": offsets}


def _parse_logit_bias(raw) -> Optional[dict]:
    if not raw:
        return None
    if not isinstance(raw, dict):
        raise ValueError("logit_bias must be a map of token id -> bias")
    return {int(k): float(v) for k, v in raw.items()}


def _parse_deadline(headers) -> Optional[float]:
    """Absolute epoch-seconds deadline from the router-propagated
    ``x-request-deadline`` header; None when absent or malformed (a
    malformed deadline must degrade to no deadline, never to a 400 —
    only the router sets this header)."""
    hdr = headers.get("x-request-deadline")
    if not hdr:
        return None
    try:
        return float(hdr)
    except ValueError:
        return None


MAX_CHOICES = 128  # OpenAI caps n at 128; batched prompts share the cap

# echo+logprobs scores the prompt with a dense teacher-forced pass whose
# attention materialises an S x S score matrix per layer — bound it
MAX_ECHO_SCORE_TOKENS = 2048


def _tokens_covering(tk, token_ids: list, text_len: int) -> int:
    """Smallest token prefix whose decode covers ``text_len`` chars.

    Used to report completion_tokens up to a stop-string cut instead of
    counting generated-but-discarded tokens. Binary search: decoded length
    is monotone non-decreasing in the token-prefix length."""
    if text_len <= 0 or not token_ids:
        return 0
    if len(tk.decode(token_ids)) < text_len:
        return len(token_ids)
    lo, hi = 1, len(token_ids)
    while lo < hi:
        mid = (lo + hi) // 2
        if len(tk.decode(token_ids[:mid])) >= text_len:
            hi = mid
        else:
            lo = mid + 1
    return lo


# endpoint families this engine ACTUALLY serves, advertised on the
# /v1/models card so the router can refuse unsupported modalities
# (audio/images) with a clean 501 instead of letting them die here
# (router/request_service.py PATH_CAPABILITY; VERDICT r3 #5)
ENGINE_CAPABILITIES = (
    "chat", "completions", "responses", "messages", "embeddings",
    "score", "rerank", "pooling", "tokenize",
)


class EngineServer:
    def __init__(self, config: EngineConfig, engine: Optional[LLMEngine] = None,
                 warmup_on_start: bool = False,
                 overload_retry_after: float = 1.0,
                 otel_endpoint: Optional[str] = None,
                 otel_service_name: str = "tpu-engine",
                 otel_secure: bool = False,
                 flight_recorder_size: int = 256,
                 drain_deadline: float = 30.0,
                 watchdog_stall_seconds: float = 0.0,
                 diagnostics: Optional[DiagnosticsConfig] = None,
                 brownout: Optional[BrownoutController] = None):
        self.config = config
        self.warmup_on_start = warmup_on_start
        self.model_name = config.model.name
        self.engine = engine or LLMEngine(config)
        # this replica's start in parts (engine/tracing.py StartClock; the
        # engine's own, or `main()`'s handed through it): from here to the
        # end of `_on_start`, which the socket's binding follows at once,
        # is `server_bind`; the warm-up's seconds and `startup_seconds` of
        # /debug/perf are read from it and kept nowhere else
        self.start = self.engine.start
        self._bind_span: Optional[int] = self.start.begin("server_bind")
        self.async_engine = AsyncEngine(self.engine)
        self.metrics = ServerMetrics(self.engine, self.model_name)
        self.async_engine.step_observer = self.metrics.observe_step
        etracing.initialize_tracing(otel_endpoint, otel_service_name,
                                    otel_secure)
        self.flight_recorder = FlightRecorder(flight_recorder_size)
        self._inflight: dict = {}  # root rid → open flight record
        # a request's time to first token in parts, summed as its record
        # closes, and the event loop's heartbeat against the engine
        # thread's phases (engine/tracing.py): always on, read at scrape
        self.ttft_parts = etracing.TtftParts()
        self.loop_lag = etracing.LoopLag(self.engine.clock)
        self.metrics.register_request_path(self.ttft_parts, self.loop_lag)
        # pushed P→D transfers awaiting their decode hop: transfer id →
        # {blocks, layers_done, meta, created, ready}. Blocks are owned by
        # this table until the attach splices them into a sequence (then
        # the scheduler owns them) or the TTL sweep frees them.
        self._kv_transfers: dict = {}
        # strong refs to fire-and-forget tasks (TTL-sweep block frees):
        # the loop holds tasks weakly, so an unreferenced task can be
        # GC-cancelled mid-flight and its exception silently dropped
        self._bg_tasks: set = set()
        # Floor for the Retry-After seconds advertised on overload 429s;
        # the actual value is derived from the admission queue's depth and
        # recent drain rate (scheduler.retry_after_hint), so a deep queue
        # advertises a proportionally longer backoff. The router's circuit
        # breaker uses it as the ejection cooldown.
        self.overload_retry_after = overload_retry_after
        # staged brownout degradation (engine/overload.py): evaluated on
        # its own asyncio loop against scheduler depth / HBM occupancy /
        # watchdog state; None = feature off (default)
        self.brownout = brownout
        self._brownout_task: Optional[asyncio.Task] = None
        # stage-3 shed set, recomputed each evaluation from live per-tenant
        # scheduler load (overweight_tenants); admission checks membership
        self._brownout_shed: set = set()
        self._shed_counts_seen = {"spec": 0, "prefetch": 0}
        from production_stack_tpu.engine.lora import LoraManager

        self.lora = LoraManager(self.engine)
        # durable per-request usage ledger (tenancy.UsageLedger): rotating
        # JSONL written on request finish. Off unless metering is on AND a
        # path was configured — the in-memory attribution plane does not
        # depend on it.
        self.usage_ledger = None
        if config.tenant_metering and config.tenant_ledger_path:
            from production_stack_tpu.tenancy import UsageLedger

            self.usage_ledger = UsageLedger(
                config.tenant_ledger_path,
                max_bytes=config.tenant_ledger_max_bytes,
            )
        self._perf_fp: Optional[dict] = None
        self.start_time = time.time()
        # -- fleet lifecycle: drain state machine + stuck-step watchdog.
        # SERVING → DRAINING (SIGTERM / POST /drain): readiness (GET
        # /ready) answers 503 while /health stays truthful, new generation
        # requests get 503 + Retry-After, in-flight sequences finish under
        # drain_deadline, stragglers are then aborted (KV blocks freed).
        self.drain_deadline = drain_deadline
        self.draining = False
        self.drain_reason: Optional[str] = None
        # WARMING precedes SERVING: a fresh TPU replica must run its
        # warmup compiles (all shape variants) before it is fit for
        # traffic — /ready answers 503 {"status": "warming"} until they
        # finish, so service discovery (and therefore the autoscaler's
        # scale-ups) never cuts a cold replica into the ring. /health
        # stays 200 the whole time: the pod is alive, just not ready.
        self.warming = False
        # a warm-up that raised: /ready stays 503 for good and main() exits
        # non-zero once run_app unwinds
        self.warmup_error: Optional[str] = None
        self._warmup_task: Optional[asyncio.Task] = None
        # main() flips this on before run_app so SIGTERM drains instead of
        # killing the loop; in-process test servers leave it off.
        self.drain_on_sigterm = False
        self._drain_t0: Optional[float] = None
        self._drain_task: Optional[asyncio.Task] = None
        self._exit_task: Optional[asyncio.Task] = None
        self._drain_rejected = 0
        self._drain_aborted = 0
        self.watchdog = StepWatchdog(self.async_engine,
                                     watchdog_stall_seconds)
        self.metrics.register_lifecycle(self._lifecycle_snapshot)
        self.metrics.register_overload(self._overload_snapshot)
        # -- anomaly-triggered diagnostic bundles (engine/diagnostics.py):
        # subscribe the capture manager to the bug signals this server
        # already raises — unexpected recompile, watchdog stall, drain-
        # deadline abort, HBM pressure — so each one leaves evidence
        # (perf/KV snapshot, flight recorder, compile tail, memory
        # profile, optional short jax trace) at GET /debug/diagnostics.
        # All capture work runs on the manager's own thread: the serving
        # loop only ever pays for a non-blocking trigger() call.
        self.diagnostics = DiagnosticsManager(
            diagnostics if diagnostics is not None else DiagnosticsConfig(),
            tier="engine",
            collectors={
                "perf.json": self._collect_perf,
                "lifecycle.json": self._lifecycle_snapshot,
                "flight_recorder.json": self._collect_flight_recorder,
                "scheduler.json": self._collect_scheduler,
                "compile_events.json": self._collect_compile_tail,
                "memory.pprof": self._collect_device_memory,
            },
            profile_fn=self._diag_profile,
        )
        if self.diagnostics.config.enabled:
            perf = getattr(self.engine, "perf", None)
            if perf is not None:
                perf.anomaly_hook = self.diagnostics.trigger
                perf.hbm_threshold = self.diagnostics.config.hbm_threshold
            self.watchdog.on_stall = (
                lambda d: self.diagnostics.trigger("watchdog_stall", d))
            # recovery is a fact worth indexing, not worth a second
            # bundle — the stall capture already holds the evidence
            self.watchdog.on_recover = (
                lambda d: self.diagnostics.note("watchdog_recovered", d))
            self.metrics.register_diagnostics(self.diagnostics.stats)

    # -- app assembly --------------------------------------------------------
    def build_app(self) -> web.Application:
        import os

        from production_stack_tpu.testing.faults import (
            FaultSpec,
            FaultState,
            fault_middleware,
        )

        # fault injection is an explicit opt-in: the middleware AND the
        # live /debug/faults toggle exist only when the operator set
        # FAULT_INJECTION (any value — "" arms the toggle with no faults);
        # a production engine without it has no injectable surface at all
        self._faults_armed = "FAULT_INJECTION" in os.environ
        spec = os.environ.get("FAULT_INJECTION", "")
        self.faults = FaultState(FaultSpec.parse(spec) if spec else None)
        if self.faults.spec is not None:
            import logging

            logging.getLogger(__name__).warning(
                "FAULT INJECTION ACTIVE: %s", self.faults.spec
            )
        middlewares = (
            [fault_middleware(self.faults)] if self._faults_armed else []
        )
        # drain gate AFTER fault injection: chaos drills must be able to
        # exercise faults on the drain surface itself
        middlewares.append(self._drain_middleware)
        app = web.Application(client_max_size=64 * 1024 * 1024,
                              middlewares=middlewares)
        app.router.add_post("/v1/completions", self.completions)
        app.router.add_post("/v1/chat/completions", self.chat_completions)
        app.router.add_get("/v1/models", self.models)
        app.router.add_get("/health", self.health)
        app.router.add_get("/ready", self.ready)
        app.router.add_post("/drain", self.drain)
        app.router.add_get("/version", self.version)
        app.router.add_post("/tokenize", self.tokenize)
        app.router.add_post("/detokenize", self.detokenize)
        app.router.add_get("/metrics", self.prometheus)
        app.router.add_post("/kv/lookup", self.kv_lookup)
        app.router.add_post("/kv/export", self.kv_export)
        app.router.add_post("/kv/recv", self.kv_recv)
        app.router.add_post("/v1/embeddings", self.embeddings)
        app.router.add_post("/v1/score", self.score)
        app.router.add_post("/v1/rerank", self.rerank)
        app.router.add_post("/rerank", self.rerank)  # Jina-style alias
        app.router.add_post("/v1/messages", self.messages)
        app.router.add_post("/v1/responses", self.responses)
        app.router.add_post("/pooling", self.pooling)
        app.router.add_post("/v1/load_lora_adapter", self.load_lora)
        app.router.add_post("/v1/unload_lora_adapter", self.unload_lora)
        app.router.add_post("/debug/profile", self.profile)
        app.router.add_get("/debug/memory", self.memory_profile)
        app.router.add_get("/debug/perf", self.debug_perf)
        app.router.add_get("/debug/canary", self.debug_canary)
        app.router.add_get("/debug/overload", self.debug_overload)
        app.router.add_get("/debug/tenants", self.debug_tenants)
        app.router.add_get("/debug/requests", self.debug_requests)
        app.router.add_get("/debug/diagnostics", self.diagnostics_index)
        app.router.add_get("/debug/diagnostics/{bundle_id}",
                           self.diagnostics_bundle)
        app.router.add_post("/debug/diagnostics/capture",
                            self.diagnostics_capture)
        if self._faults_armed:
            app.router.add_post("/debug/faults", self.debug_faults)
        app.router.add_post("/sleep", self.sleep)
        app.router.add_post("/wake_up", self.wake_up)
        app.router.add_get("/is_sleeping", self.is_sleeping)
        app.on_startup.append(self._on_start)
        app.on_cleanup.append(self._on_stop)
        return app

    async def _on_start(self, app) -> None:
        self.metrics.ensure_registered()
        await self.async_engine.start()
        self.loop_lag.start(asyncio.get_running_loop())
        self.watchdog.start()
        if self.drain_on_sigterm:
            self._install_signal_drain()
        if self.brownout is not None and self.brownout.config.enabled:
            self._brownout_task = asyncio.ensure_future(
                self._brownout_worker())
        if self._bind_span is not None:  # an app built twice binds twice
            self.start.end(self._bind_span)
            self._bind_span = None
        if self.warmup_on_start:
            # warm in the background so the server binds immediately and
            # /ready can answer 503 {"status": "warming"} while the
            # compiles run — discovery and the autoscaler need to SEE the
            # warming state, not a connection-refused socket
            self.warming = True
            self._warmup_task = asyncio.ensure_future(
                self._run_warmup(self.start.begin("warmup")))
        else:
            self.start.mark_ready()

    async def _run_warmup(self, span: int) -> None:
        try:
            await self.async_engine.run_on_engine(lambda eng: eng.warmup())
        except Exception as e:
            # a program that does not compile or run here would fail every
            # request the same way: stay un-ready and take the process down
            # rather than serve 500s behind a 200 /ready
            self.warmup_error = f"{type(e).__name__}: {e}"
            _log.critical("engine warm-up failed after %.1fs; exiting",
                          self.start.end(span), exc_info=True)
            asyncio.get_running_loop().call_soon(self._exit)
            return
        seconds = self.start.end(span)
        self.start.mark_ready()
        self.warming = False
        print(f"engine warmup (all shape variants) done in "
              f"{seconds:.1f}s", flush=True)

    async def _on_stop(self, app) -> None:
        if self._warmup_task is not None:
            self._warmup_task.cancel()
        if self._brownout_task is not None:
            self._brownout_task.cancel()
        if self._drain_task is not None:
            self._drain_task.cancel()
        self.watchdog.stop()
        self.loop_lag.stop()
        self.async_engine.stop()
        self.metrics.unregister()

    # -- drain state machine / readiness -------------------------------------
    @web.middleware
    async def _drain_middleware(self, request: web.Request, handler):
        """While DRAINING, refuse NEW generation work with an honest 503 +
        Retry-After (the router fails the attempt over to a live backend).
        Requests already past this gate — live streams — keep running;
        infra endpoints (/health, /ready, /metrics, /v1/models, tokenize)
        stay up so probes and discovery keep seeing the truth."""
        if (self.draining and request.method == "POST"
                and (request.path.startswith("/v1/")
                     or request.path in ("/pooling", "/rerank"))):
            self._drain_rejected += 1
            return web.json_response(
                {"error": {"message": "engine is draining; no new "
                           "requests are admitted",
                           "type": "service_unavailable_error"}},
                status=503,
                headers={"Retry-After": f"{self.overload_retry_after:g}"},
            )
        return await handler(request)

    def _lifecycle_snapshot(self) -> dict:
        """Scrape-time source for the vllm:drain_* / vllm:watchdog_*
        families (engine/metrics.py LifecycleCollector)."""
        return {
            "draining": self.draining,
            "drain_rejected_total": self._drain_rejected,
            "drain_aborted_total": self._drain_aborted,
            "watchdog_stalled": self.watchdog.stalled,
            "watchdog_stalls_total": self.watchdog.stalls_total,
            "warming": self.warming,
            "start_seconds": self.start.seconds(),
            "start_to_ready_seconds": self.start.to_ready,
        }

    # -- staged brownout (engine/overload.py) --------------------------------
    async def _brownout_worker(self) -> None:
        """Periodic pressure evaluation: read the signals ON the engine
        thread (scheduler/accountant state is engine-owned), step the
        hysteretic controller, then push the stage actions back onto the
        engine thread. Everything a stage changes is host-side admission/
        grant policy — the jitted programs never see a different shape."""
        ctl = self.brownout
        assert ctl is not None
        while True:
            await asyncio.sleep(ctl.config.interval)
            try:
                sig = await self.async_engine.run_on_engine(
                    lambda eng: self._pressure_signals(eng))
                prev = ctl.stage
                ctl.evaluate(sig, time.monotonic())
                if ctl.stage != prev:
                    _log.warning(
                        "brownout stage %d -> %d (%s)", prev, ctl.stage,
                        ",".join(ctl.last_reasons) or "recovered")
                await self.async_engine.run_on_engine(
                    lambda eng: self._apply_brownout(eng))
            except asyncio.CancelledError:
                raise
            except Exception:
                _log.exception("brownout evaluation failed")

    def _pressure_signals(self, eng) -> PressureSignals:
        """Build one evaluation's signals (runs on the engine thread)."""
        sched = eng.scheduler
        qcap = max(1, int(getattr(sched.config, "max_queue_len", 0) or 0))
        qfrac = len(sched.waiting) / qcap
        hbm_frac = 0.0
        perf = getattr(eng, "perf", None)
        if perf is not None:
            hbm = getattr(perf, "_hbm", None) or {}
            total = hbm.get("total") or 0
            if total > 0:
                hbm_frac = hbm.get("used", 0) / total
        return PressureSignals(
            queue_fraction=qfrac,
            hbm_fraction=hbm_frac,
            watchdog_stalled=self.watchdog.stalled,
        )

    def _apply_brownout(self, eng) -> None:
        """Push the current stage's actions onto engine-owned state (runs
        on the engine thread) and fold the engine-side shed tallies into
        the controller's counter source."""
        ctl = self.brownout
        sched = eng.scheduler
        sched.spec_shed = ctl.shed_spec
        eng.prefetch_paused = ctl.pause_prefetch
        # engine-side tallies (grants suppressed, prefetches skipped) are
        # counted where they happen; diff them into ctl.sheds here
        for reason, attr, obj in ((SHED_SPEC, "spec_shed_count", sched),
                                  (SHED_PREFETCH, "prefetch_shed_count",
                                   eng)):
            total = getattr(obj, attr, 0)
            delta = total - self._shed_counts_seen[reason]
            if delta > 0:
                ctl.record_shed(reason, delta)
                self._shed_counts_seen[reason] = total
        if ctl.shed_overweight:
            self._brownout_shed = set(overweight_tenants(
                sched.tenant_loads(),
                getattr(sched.config, "tenant_weights", None)))
        elif self._brownout_shed:
            self._brownout_shed = set()

    def _overload_snapshot(self) -> dict:
        """Scrape-time source for vllm:brownout_* / vllm:fair_share_deficit
        (engine/metrics.py OverloadCollector) and /debug/overload."""
        ctl = self.brownout
        return {
            "brownout": (ctl.snapshot() if ctl is not None
                         else {"enabled": False, "stage": 0, "sheds": {}}),
            "shed_tenants": sorted(self._brownout_shed),
            "fair_share": self.engine.scheduler.fair_share_snapshot(),
        }

    async def debug_overload(self, request: web.Request) -> web.Response:
        return web.json_response(self._overload_snapshot())

    def _perf_fingerprint(self) -> dict:
        """What this engine is serving with, for ``/debug/perf`` (computed
        once): the fields that change the performance envelope, so that a
        reader compares runs only within one configuration."""
        if self._perf_fp is not None:
            return self._perf_fp
        cfg = self.config
        perf = getattr(self.engine, "perf", None)
        jax_version = platform = chip = ""
        n_devices = 0
        try:
            import jax

            jax_version = str(jax.__version__)
            dev = jax.local_devices()[0]
            platform = str(dev.platform)
            chip = str(getattr(dev, "device_kind", "") or "")
            n_devices = jax.device_count()
        except Exception:
            # fingerprint degrades (empty jax/chip fields), never fails
            _log.debug("perf fingerprint: no jax device identifiers")
        self._perf_fp = {
            "model": cfg.model.name,
            "role": cfg.role,
            "tensor_parallel": int(getattr(perf, "tp", 1) or 1),
            # the one way a prompt runs; the key stays for readers of
            # the fingerprint (ROADMAP D20)
            "attention_impl": "ragged",
            "dtype": cfg.model.dtype,
            "quantization": cfg.model.quant or "",
            "speculative": bool(cfg.scheduler.spec_ngram_k),
            "n_chips": int(getattr(perf, "n_chips", 1) or 1),
            "jax_version": jax_version,
            "platform": platform,
            "chip": chip,
            "n_devices": n_devices,
            # the attention path actually taken: False on an accelerator
            # is the XLA gather slow path (model_runner._pallas_ok)
            "use_pallas": bool(getattr(
                getattr(self.engine, "runner", None), "use_pallas", False)),
        }
        return self._perf_fp

    def begin_drain(self, reason: str) -> bool:
        """Flip SERVING → DRAINING (idempotent; returns False when already
        draining) and start the drain watcher."""
        if self.draining:
            return False
        self.draining = True
        self.drain_reason = reason
        self._drain_t0 = time.monotonic()
        _log.warning(
            "drain started (%s): %d in-flight request(s), deadline %.1fs",
            reason, len(self._inflight), self.drain_deadline,
        )
        self._drain_task = asyncio.ensure_future(self._drain_watch())
        return True

    async def _drain_watch(self) -> None:
        """Let in-flight work run to completion under the drain deadline;
        abort stragglers through the same path as deadline expiry so KV
        blocks are always freed and the process can exit bounded."""
        assert self._drain_t0 is not None
        deadline = self._drain_t0 + self.drain_deadline
        while time.monotonic() < deadline:
            if not self._inflight and not self.engine.has_unfinished():
                _log.warning("drain complete in %.2fs: no in-flight work",
                             time.monotonic() - self._drain_t0)
                return
            await asyncio.sleep(0.05)
        # deadline expired — abort every sequence the scheduler still
        # holds. Direct read + intake-queue abort (not run_on_engine): a
        # wedged engine thread must not be able to hang the drain path.
        rids = self.engine.live_request_ids()
        for rid in rids:
            self.async_engine.abort(rid)
        self._drain_aborted += len(rids)
        if rids:
            _log.warning(
                "drain deadline (%.1fs) expired: aborted %d straggler "
                "sequence(s); their KV blocks are freed",
                self.drain_deadline, len(rids),
            )
            self.diagnostics.trigger("drain_deadline_abort", {
                "aborted": len(rids),
                "deadline_seconds": self.drain_deadline,
                "reason": self.drain_reason,
            })

    def _install_signal_drain(self) -> None:
        """Replace run_app's immediate-GracefulExit SIGTERM handler with
        the drain path: K8s scale-down delivers SIGTERM and grants
        terminationGracePeriodSeconds — exit only after the drain watcher
        finished (or aborted) the in-flight work. SIGINT keeps the
        immediate path (operator ctrl-C); signals arriving before the loop
        runs are covered by main()'s pre-loop handler."""
        import signal as _signal

        loop = asyncio.get_running_loop()
        try:
            loop.add_signal_handler(_signal.SIGTERM, self._on_sigterm)
        except (NotImplementedError, RuntimeError):
            pass  # non-unix: keep run_app's default handler

    def _on_sigterm(self) -> None:
        # The drain may already be running — the preStop hook POSTs
        # /drain before kubelet delivers SIGTERM — but only SIGTERM owns
        # process exit: an API drain must still terminate the pod once
        # the signal lands, or it lingers until SIGKILL.
        self.begin_drain("sigterm")
        if self._exit_task is None:
            self._exit_task = asyncio.ensure_future(
                self._exit_when_drained())

    async def _exit_when_drained(self) -> None:
        if self._drain_task is not None:
            try:
                await self._drain_task
            except asyncio.CancelledError:
                return  # server torn down underneath us
        # one beat for handlers to deliver final bytes / observe aborts
        # before the server tears down
        await asyncio.sleep(0.1)
        asyncio.get_running_loop().call_soon(self._exit)

    def _exit(self) -> None:
        """Raise GracefulExit out of run_forever → run_app's cleanup path
        (on_cleanup → _on_stop). Called as a plain
        loop callback so the BaseException propagates; tests replace this
        attribute to observe exit without killing their loop."""
        from aiohttp.web_runner import GracefulExit

        raise GracefulExit()

    # -- infra endpoints ------------------------------------------------------
    async def health(self, request: web.Request) -> web.Response:
        return web.json_response({"status": "healthy"})

    async def ready(self, request: web.Request) -> web.Response:
        """Readiness, distinct from /health liveness: 503 while DRAINING
        (stop sending new work; do NOT restart — live streams are
        finishing) and while the stuck-step watchdog sees a wedged engine
        (alive for debugging, unfit for traffic)."""
        if self.draining:
            remaining = 0.0
            if self._drain_t0 is not None:
                remaining = max(
                    0.0,
                    self._drain_t0 + self.drain_deadline - time.monotonic())
            return web.json_response(
                {"status": "draining", "reason": self.drain_reason,
                 "inflight": len(self._inflight),
                 "deadline_remaining": round(remaining, 3)},
                status=503,
            )
        if self.warmup_error is not None:
            return web.json_response(
                {"status": "warmup_failed", "error": self.warmup_error},
                status=503,
            )
        if self.warming:
            elapsed = self.start.open_since("warmup") or 0.0
            return web.json_response(
                {"status": "warming", "warming_for": round(elapsed, 3)},
                status=503,
            )
        if self.watchdog.stalled:
            return web.json_response(
                {"status": "stalled",
                 "stalled_for": round(self.watchdog.progress_age(), 3)},
                status=503,
            )
        return web.json_response({"status": "ready"})

    async def drain(self, request: web.Request) -> web.Response:
        """Begin draining (idempotent). The helm preStop hook POSTs here
        so new work stops flowing before K8s delivers SIGTERM; the SIGTERM
        path owns the actual process exit."""
        started = self.begin_drain("api")
        return web.json_response({
            "status": "draining",
            "already_draining": not started,
            "deadline": self.drain_deadline,
            "inflight": len(self._inflight),
        })

    async def version(self, request: web.Request) -> web.Response:
        return web.json_response({"version": __version__})

    async def models(self, request: web.Request) -> web.Response:
        cards = [
            {
                "id": self.model_name,
                "object": "model",
                "created": int(self.start_time),
                "owned_by": "production-stack-tpu",
                "root": self.model_name,
                "parent": None,
                "max_model_len": self.config.model.max_model_len,
                "capabilities": list(ENGINE_CAPABILITIES),
                "role": getattr(self.config, "role", "unified"),
            }
        ]
        for name in self.lora.list_adapters():
            cards.append(
                {
                    "id": name,
                    "object": "model",
                    "created": int(self.start_time),
                    "owned_by": "production-stack-tpu",
                    "root": self.model_name,
                    "parent": self.model_name,
                }
            )
        return web.json_response({"object": "list", "data": cards})

    async def messages(self, request: web.Request) -> web.StreamResponse:
        """Anthropic-style Messages API (the reference proxies /v1/messages
        to engines, main_router.py; here it's served natively)."""
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": {"message": "invalid JSON"}},
                                     status=400)
        msgs = body.get("messages")
        if not msgs:
            return web.json_response(
                {"error": {"message": "'messages' is required"}}, status=400
            )
        chat = []
        if body.get("system"):
            chat.append({"role": "system", "content": body["system"]})
        for m in msgs:
            content = m.get("content")
            if isinstance(content, list):
                content = "".join(
                    b.get("text", "") for b in content if b.get("type") == "text"
                )
            chat.append({"role": m.get("role", "user"), "content": content})
        prompt = self._render_chat(chat)
        prompt_ids = self.engine.tokenizer.encode(prompt)
        if body.get("stop_sequences"):  # Anthropic-spec field name
            body = dict(body, stop=body["stop_sequences"])
        try:
            sampling = _sampling_from_body(body)
            make_token_controls(sampling, self.config.model.vocab_size)
        except (TypeError, ValueError) as e:
            return web.json_response(
                {"error": {"message": f"invalid sampling parameter: {e}"}},
                status=400,
            )
        rid = f"msg_{uuid.uuid4().hex[:24]}"

        if len(prompt_ids) > self.config.model.max_model_len - 1:
            return web.json_response(
                {"error": {"message": "prompt too long"}}, status=400
            )
        gen = self.async_engine.generate(
            prompt_ids, sampling, rid,
            adapter_slot=self.lora.slot_of(body.get("model", "")),
        )
        tk = self.engine.tokenizer

        if body.get("stream"):
            resp = web.StreamResponse(
                headers={"Content-Type": "text/event-stream"}
            )
            await resp.prepare(request)

            async def ev(name, payload):
                await resp.write(
                    f"event: {name}\ndata: {json.dumps(payload)}\n\n".encode()
                )

            await ev("message_start", {
                "type": "message_start",
                "message": {"id": rid, "type": "message", "role": "assistant",
                            "model": body.get("model", self.model_name),
                            "content": [],
                            "usage": {"input_tokens": len(prompt_ids)}},
            })
            await ev("content_block_start", {
                "type": "content_block_start", "index": 0,
                "content_block": {"type": "text", "text": ""},
            })
            token_ids, sent = [], 0
            n_out = 0
            finish = "end_turn"
            async for out in gen:
                token_ids.extend(out.new_token_ids)
                n_out = out.num_output_tokens
                text = tk.decode(token_ids)
                stopped = self._check_stop_str(text, sampling)
                if stopped is not None:
                    self.async_engine.abort(rid)
                    text = stopped
                    finish = "stop_sequence"
                if len(text) > sent:
                    await ev("content_block_delta", {
                        "type": "content_block_delta", "index": 0,
                        "delta": {"type": "text_delta", "text": text[sent:]},
                    })
                    sent = len(text)
                if stopped is not None:
                    break
                if out.finished:
                    finish = ("max_tokens" if out.finish_reason == "length"
                              else "end_turn")
            await ev("content_block_stop",
                     {"type": "content_block_stop", "index": 0})
            await ev("message_delta", {
                "type": "message_delta",
                "delta": {"stop_reason": finish},
                "usage": {"output_tokens": n_out},
            })
            await ev("message_stop", {"type": "message_stop"})
            await resp.write_eof()
            return resp

        token_ids = []
        finish = "end_turn"
        text = ""
        async for out in gen:
            token_ids.extend(out.new_token_ids)
            text = tk.decode(token_ids)
            stopped = self._check_stop_str(text, sampling)
            if stopped is not None:
                self.async_engine.abort(rid)
                text = stopped
                finish = "stop_sequence"
                break
            if out.finished:
                finish = ("max_tokens" if out.finish_reason == "length"
                          else "end_turn")
        return web.json_response({
            "id": rid, "type": "message", "role": "assistant",
            "model": body.get("model", self.model_name),
            "content": [{"type": "text", "text": text}],
            "stop_reason": finish,
            "usage": {"input_tokens": len(prompt_ids),
                      "output_tokens": len(token_ids)},
        })

    async def responses(self, request: web.Request) -> web.StreamResponse:
        """OpenAI Responses API, text modality (the reference proxies
        /v1/responses to engines, main_router.py:51-301 there; here it is
        served natively — VERDICT r3 #5). Accepts ``input`` as a string or
        a message-item list plus ``instructions``; emits the Responses
        object shape, streaming (response.created /
        response.output_text.delta / response.completed events) or not."""
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": {"message": "invalid JSON"}},
                                     status=400)
        raw = body.get("input")
        if raw is None:
            return web.json_response(
                {"error": {"message": "'input' is required"}}, status=400
            )
        chat = []
        if body.get("instructions"):
            chat.append({"role": "system", "content": body["instructions"]})
        if isinstance(raw, str):
            chat.append({"role": "user", "content": raw})
        elif isinstance(raw, list):
            for item in raw:
                if not isinstance(item, dict):
                    return web.json_response(
                        {"error": {"message": "input items must be objects"}},
                        status=400,
                    )
                if item.get("type") not in (None, "message"):
                    return web.json_response(
                        {"error": {
                            "message": f"unsupported input item type "
                                       f"{item.get('type')!r}: this engine "
                                       "serves the text modality only",
                            "type": "invalid_request_error"}},
                        status=400,
                    )
                content = item.get("content")
                if isinstance(content, list):
                    if not all(isinstance(b, dict) for b in content):
                        return web.json_response(
                            {"error": {"message": "content parts must be "
                                       "objects",
                                       "type": "invalid_request_error"}},
                            status=400,
                        )
                    content = "".join(
                        b.get("text", "") for b in content
                        if b.get("type") in ("input_text", "output_text",
                                             "text")
                    )
                chat.append({"role": item.get("role", "user"),
                             "content": content or ""})
        else:
            return web.json_response(
                {"error": {"message": "'input' must be a string or list"}},
                status=400,
            )
        prompt_ids = self.engine.tokenizer.encode(self._render_chat(chat))
        if len(prompt_ids) > self.config.model.max_model_len - 1:
            return web.json_response(
                {"error": {"message": "input too long"}}, status=400
            )
        if body.get("max_output_tokens") is not None:
            body = dict(body, max_tokens=body["max_output_tokens"])
        try:
            sampling = _sampling_from_body(body)
            make_token_controls(sampling, self.config.model.vocab_size)
        except (TypeError, ValueError) as e:
            return web.json_response(
                {"error": {"message": f"invalid sampling parameter: {e}",
                           "type": "invalid_request_error"}},
                status=400,
            )
        rid = f"resp_{uuid.uuid4().hex[:24]}"
        msg_id = f"msg_{uuid.uuid4().hex[:24]}"
        created = int(time.time())
        model = body.get("model", self.model_name)
        gen = self.async_engine.generate(
            prompt_ids, sampling, rid,
            adapter_slot=self.lora.slot_of(body.get("model", "")),
        )
        tk = self.engine.tokenizer

        def response_obj(status, text, n_out, incomplete=None):
            return {
                "id": rid, "object": "response", "created_at": created,
                "status": status, "model": model, "error": None,
                "incomplete_details": incomplete,
                "instructions": body.get("instructions"),
                "max_output_tokens": body.get("max_output_tokens"),
                "output": [{
                    "type": "message", "id": msg_id, "status": status,
                    "role": "assistant",
                    "content": [{"type": "output_text", "text": text,
                                 "annotations": []}],
                }],
                "temperature": sampling.temperature,
                "top_p": sampling.top_p,
                "usage": {"input_tokens": len(prompt_ids),
                          "output_tokens": n_out,
                          "total_tokens": len(prompt_ids) + n_out},
            }

        if body.get("stream"):
            resp = web.StreamResponse(
                headers={"Content-Type": "text/event-stream"}
            )
            await resp.prepare(request)
            seq = 0

            async def ev(name, payload):
                nonlocal seq
                payload = dict(payload, type=name, sequence_number=seq)
                seq += 1
                await resp.write(
                    f"event: {name}\ndata: {json.dumps(payload)}\n\n".encode()
                )

            await ev("response.created",
                     {"response": response_obj("in_progress", "", 0)})
            await ev("response.output_item.added", {
                "output_index": 0,
                "item": {"type": "message", "id": msg_id,
                         "status": "in_progress", "role": "assistant",
                         "content": []},
            })
            # stop sequences can span step boundaries: hold back enough
            # trailing chars that a stop prefix is never streamed before
            # it is confirmed not to be one (same mechanism as the
            # chat/completions stream path)
            holdback = max((len(s) for s in sampling.stop), default=1) - 1
            token_ids, sent = [], 0
            n_out = 0
            text = ""
            incomplete = None
            hit_stop = False
            async for out in gen:
                token_ids.extend(out.new_token_ids)
                text = tk.decode(token_ids)
                stopped = self._check_stop_str(text, sampling)
                if stopped is not None:
                    self.async_engine.abort(rid)
                    text = stopped
                    n_out = _tokens_covering(tk, token_ids, len(stopped))
                    hit_stop = True
                else:
                    n_out = len(token_ids)
                done = out.finished or hit_stop
                limit = (len(text) if done or not holdback
                         else max(sent, len(text) - holdback))
                if limit > sent:
                    await ev("response.output_text.delta", {
                        "item_id": msg_id, "output_index": 0,
                        "content_index": 0, "delta": text[sent:limit],
                    })
                    sent = limit
                if hit_stop:
                    break
                if out.finished and out.finish_reason == "length":
                    incomplete = {"reason": "max_output_tokens"}
            await ev("response.output_text.done", {
                "item_id": msg_id, "output_index": 0, "content_index": 0,
                "text": text,
            })
            final = response_obj(
                "incomplete" if incomplete else "completed", text, n_out,
                incomplete,
            )
            await ev("response.completed", {"response": final})
            await resp.write_eof()
            return resp

        token_ids = []
        text = ""
        incomplete = None
        n_out = 0
        async for out in gen:
            token_ids.extend(out.new_token_ids)
            text = tk.decode(token_ids)
            stopped = self._check_stop_str(text, sampling)
            if stopped is not None:
                self.async_engine.abort(rid)
                text = stopped
                # usage counts only the tokens whose text survived the
                # stop-string cut (same as the completions path)
                n_out = _tokens_covering(tk, token_ids, len(stopped))
                break
            n_out = len(token_ids)
            if out.finished and out.finish_reason == "length":
                incomplete = {"reason": "max_output_tokens"}
        return web.json_response(response_obj(
            "incomplete" if incomplete else "completed", text,
            n_out, incomplete,
        ))

    def _encode_ids(self, text) -> list[int]:
        """Shared encoder-input pipeline for embeddings/score/rerank:
        str -> tokenize; list of ints -> pre-tokenized; anything else is
        the caller's validation problem. Truncated to max_model_len - 1."""
        tk = self.engine.tokenizer
        ids = tk.encode(text) if isinstance(text, str) else list(text)
        return ids[: self.config.model.max_model_len - 1]

    async def _pair_scores(self, query, documents):
        """Cosine similarity of pooled hidden states (the causal-LM
        fallback scorer, matching the /v1/embeddings encoder). Returns
        (scores, total_tokens)."""
        import numpy as np

        total = 0

        async def vec(text):
            nonlocal total
            ids = self._encode_ids(text)
            total += len(ids)
            return await self.async_engine.run_on_engine(
                lambda eng, ids=ids: eng.embed(ids)
            )

        q = np.asarray(await vec(query), np.float32)
        qn = q / max(float(np.linalg.norm(q)), 1e-9)
        out = []
        for doc in documents:
            d = np.asarray(await vec(doc), np.float32)
            dn = d / max(float(np.linalg.norm(d)), 1e-9)
            out.append(float(qn @ dn))
        return out, total

    async def score(self, request: web.Request) -> web.Response:
        """vLLM-style /v1/score: similarity of text_1 against each text_2
        (the reference router proxies this endpoint; here it's native)."""
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": {"message": "invalid JSON"}},
                                     status=400)
        t1 = body.get("text_1")
        t2 = body.get("text_2")
        if t1 is None or t2 is None:
            return web.json_response(
                {"error": {"message": "'text_1' and 'text_2' are required"}},
                status=400,
            )
        # vLLM accepts str-or-list on both sides; lists of strings are
        # queries, not token ids
        queries = t1 if isinstance(t1, list) else [t1]
        docs = t2 if isinstance(t2, list) else [t2]
        if not all(isinstance(x, str) for x in queries + docs):
            return web.json_response(
                {"error": {"message": "text_1/text_2 must be strings or "
                           "lists of strings"}},
                status=400,
            )
        if len(queries) == 1:
            scores, total = await self._pair_scores(queries[0], docs)
        elif len(queries) == len(docs):  # pairwise form
            scores, total = [], 0
            for q, d in zip(queries, docs):
                s, t = await self._pair_scores(q, [d])
                scores.append(s[0])
                total += t
        else:
            return web.json_response(
                {"error": {"message": "text_1 list must have length 1 or "
                           "match text_2"}},
                status=400,
            )
        return web.json_response({
            "id": f"score-{uuid.uuid4().hex[:16]}",
            "object": "list",
            "model": body.get("model", self.model_name),
            "data": [{"object": "score", "index": i, "score": s}
                     for i, s in enumerate(scores)],
            "usage": {"total_tokens": total},
        })

    async def rerank(self, request: web.Request) -> web.Response:
        """Jina/Cohere-style rerank: order documents by relevance to the
        query (served natively; reference: /rerank and /v1/rerank in its
        proxy list, main_router.py)."""
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": {"message": "invalid JSON"}},
                                     status=400)
        query = body.get("query")
        documents = body.get("documents")
        if not query or not isinstance(documents, list) or not documents:
            return web.json_response(
                {"error": {"message":
                           "'query' and a non-empty 'documents' list are "
                           "required"}},
                status=400,
            )
        # Cohere/Jina allow documents as strings OR {"text": ...} objects
        texts = [d.get("text") if isinstance(d, dict) else d
                 for d in documents]
        if not all(isinstance(t, str) for t in texts):
            return web.json_response(
                {"error": {"message": "documents must be strings or "
                           "objects with a 'text' field"}},
                status=400,
            )
        try:
            top_n = int(body.get("top_n") or len(texts))
        except (TypeError, ValueError):
            return web.json_response(
                {"error": {"message": "'top_n' must be an integer"}},
                status=400,
            )
        if top_n < 1:
            return web.json_response(
                {"error": {"message": "'top_n' must be >= 1"}}, status=400
            )
        scores, total = await self._pair_scores(query, texts)
        order = sorted(range(len(texts)), key=lambda i: -scores[i])
        results = [
            {"index": i, "relevance_score": scores[i],
             **({"document": {"text": texts[i]}}
                if body.get("return_documents", True) else {})}
            for i in order[:top_n]
        ]
        return web.json_response({
            "id": f"rerank-{uuid.uuid4().hex[:16]}",
            "model": body.get("model", self.model_name),
            "results": results,
            "usage": {"total_tokens": total},
        })

    async def _embed_batch(self, request: web.Request, item_of):
        """Shared /v1/embeddings + /pooling implementation: validate the
        OpenAI ``input`` shapes (str | [str,...] | [int,...] | [[int],..]),
        mean-pool each prompt through the engine, and format items via
        ``item_of(index, vector)``. Returns the response (400s included)."""
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": {"message": "invalid JSON"}},
                                     status=400)
        inputs = body.get("input")
        if inputs is None:
            return web.json_response(
                {"error": {"message": "'input' is required"}}, status=400
            )
        if isinstance(inputs, str):
            inputs = [inputs]
        elif isinstance(inputs, list) and inputs and isinstance(inputs[0], int):
            inputs = [inputs]  # a single pre-tokenized prompt
        if not isinstance(inputs, list) or not all(
            isinstance(t, str)
            or (isinstance(t, list) and all(isinstance(x, int) for x in t))
            for t in inputs
        ):
            return web.json_response(
                {"error": {"message": "invalid 'input': expected string, "
                           "token list, or a list thereof",
                           "type": "invalid_request_error"}},
                status=400,
            )
        data = []
        total_tokens = 0
        for i, text in enumerate(inputs):
            ids = self._encode_ids(text)
            total_tokens += len(ids)
            vec = await self.async_engine.run_on_engine(
                lambda eng, ids=ids: eng.embed(ids)
            )
            data.append(item_of(i, vec))
        return web.json_response(
            {
                "object": "list",
                "model": body.get("model", self.model_name),
                "data": data,
                "usage": {"prompt_tokens": total_tokens,
                          "total_tokens": total_tokens},
            }
        )

    async def embeddings(self, request: web.Request) -> web.Response:
        return await self._embed_batch(
            request,
            lambda i, vec: {"object": "embedding", "index": i,
                            "embedding": [float(x) for x in vec]},
        )

    async def pooling(self, request: web.Request) -> web.Response:
        """vLLM-style /pooling: raw pooled hidden states (the reference
        router proxies this path to vLLM pods, main_router.py there; here
        it's native — same encoder as /v1/embeddings, vLLM's response
        shape with ``data`` holding the vectors)."""
        return await self._embed_batch(
            request,
            lambda i, vec: {"object": "pooling", "index": i,
                            "data": [float(x) for x in vec]},
        )

    # -- LoRA (reference operator contract: loadadapter_controller.go:553) --
    async def load_lora(self, request: web.Request) -> web.Response:
        body = await request.json()
        name, path = body.get("lora_name"), body.get("lora_path")
        if not name or not path:
            return web.json_response(
                {"error": {"message": "lora_name and lora_path required"}},
                status=400,
            )
        try:
            await self.async_engine.run_on_engine(
                lambda eng: self.lora.load(name, path)
            )
        except Exception as e:
            return web.json_response({"error": {"message": str(e)}}, status=400)
        return web.json_response({"status": "loaded", "lora_name": name})

    async def unload_lora(self, request: web.Request) -> web.Response:
        body = await request.json()
        name = body.get("lora_name")
        ok = await self.async_engine.run_on_engine(
            lambda eng: self.lora.unload(name)
        )
        if not ok:
            return web.json_response(
                {"error": {"message": f"adapter {name!r} not loaded"}}, status=404
            )
        return web.json_response({"status": "unloaded", "lora_name": name})

    async def prometheus(self, request: web.Request) -> web.Response:
        return web.Response(
            body=self.metrics.generate(),
            content_type=CONTENT_TYPE_LATEST.split(";")[0],
        )

    async def debug_requests(self, request: web.Request) -> web.Response:
        """Flight recorder: recent per-request timelines (newest first) so
        a slow request can be dissected after the fact without a tracing
        backend. ?limit=N bounds the response."""
        try:
            limit = int(request.query["limit"]) if "limit" in request.query \
                else None
        except ValueError:
            limit = None
        return web.json_response({
            "recorder": self.flight_recorder.stats(),
            "requests": self.flight_recorder.snapshot(limit),
        })

    async def tokenize(self, request: web.Request) -> web.Response:
        body = await request.json()
        text = body.get("prompt") or body.get("text") or ""
        ids = self.engine.tokenizer.encode(text, add_bos=bool(body.get("add_special_tokens", True)))
        return web.json_response({"tokens": ids, "count": len(ids),
                                  "max_model_len": self.config.model.max_model_len})

    async def kv_lookup(self, request: web.Request) -> web.Response:
        """KV-aware routing contract: how many tokens of this prompt would
        prefix-hit the paged HBM cache right now. Answered from the
        allocator's content-hash table — the TPU-native replacement for the
        reference's LMCache controller LookupMsg channel
        (src/vllm_router/routers/routing_logic.py:377-405)."""
        body = await request.json()
        if "tokens" in body:
            ids = list(body["tokens"])
        else:
            ids = self.engine.tokenizer.encode(body.get("prompt") or "")
        _, matched = self.engine.scheduler.allocator.match_prefix(ids)
        out = {"matched_tokens": matched, "total_tokens": len(ids)}
        host_kv = getattr(self.engine, "host_kv", None)
        if host_kv is not None:
            # per-tier cached-prefix report: blocks the host tier could
            # extend the HBM match with (KV-aware routers weight a host
            # continuation below an HBM hit but far above a re-prefill)
            bs = self.config.cache.block_size
            n = host_kv.probe_extension(ids, matched // bs)
            out["matched_tokens_host"] = n * bs
        return web.json_response(out)

    async def kv_export(self, request: web.Request) -> web.Response:
        """Disaggregated-prefill KV handoff, producer side: stream the raw
        (L, n, bs, 2KH, D) slab for the requested blocks. The reference moves
        these bytes with NIXL/UCX (deployment-vllm-multi.yaml:304-335); here
        the transport is HTTP between engine pods — same block identity,
        zero extra deps. Blocks stay content-addressed after a sequence
        finishes, so recently-prefilled context is exportable until evicted."""
        body = await request.json()
        blocks = [int(b) for b in body.get("blocks", [])]
        if not blocks or any(
            b < 0 or b >= self.engine.runner.num_blocks for b in blocks
        ):
            return web.json_response(
                {"error": {"message": "invalid block ids"}}, status=400
            )
        if body.get("stream"):
            # chunked layer-group stream: device gather of group i+1
            # overlaps the network send of group i (kv_transfer.py)
            from production_stack_tpu.engine.kv_transfer import (
                default_group,
                produce_frames,
            )

            cfg = self.config
            group = max(1, min(
                int(body.get("group_layers")
                    or default_group(cfg.model.cache_layers)),
                cfg.model.cache_layers,
            ))
            shape = cfg.model.kv_pool_shape(len(blocks),
                                            cfg.cache.block_size)
            resp = web.StreamResponse(headers={
                "Content-Type": "application/octet-stream",
                "X-KV-Shape": ",".join(map(str, shape)),
                "X-KV-Dtype": str(cfg.model.dtype),
                "X-KV-Group-Layers": str(group),
            })
            # pin for the stream's duration: layer groups are gathered in
            # separate engine ops with serving steps interleaved — an
            # eviction mid-stream would hand the consumer a torn,
            # layer-inconsistent export it then commits as cache content
            await self.async_engine.run_on_engine(
                lambda eng: eng.scheduler.allocator.pin_blocks(blocks)
            )
            try:
                await resp.prepare(request)
                async for frame in produce_frames(
                    self.async_engine.run_on_engine, blocks,
                    cfg.model.cache_layers, group,
                ):
                    await resp.write(frame)
                await resp.write_eof()
            finally:
                await self.async_engine.run_on_engine(
                    lambda eng: eng.scheduler.allocator.free_blocks(blocks)
                )
            return resp
        data = await self.async_engine.run_on_engine(
            lambda eng: eng.export_kv(blocks)
        )
        return web.Response(
            body=data.tobytes(),
            content_type="application/octet-stream",
            headers={
                "X-KV-Shape": ",".join(map(str, data.shape)),
                "X-KV-Dtype": str(data.dtype),
            },
        )

    async def _maybe_import_kv(self, body: dict, prompt_ids: list[int]) -> None:
        """Consumer side of the P→D handoff: fetch the producer's blocks and
        inject them as prefix-cache content, so admission skips recompute of
        everything but the final prompt token."""
        params = body.get("kv_transfer_params") or {}
        host = params.get("remote_host")
        blocks = params.get("remote_block_ids")
        if not host or not blocks:
            return
        import aiohttp

        from production_stack_tpu.engine.kv_transfer import consume_frames

        local = None
        try:
            # reserve local blocks up front so scatters stream straight in
            got = await self.async_engine.run_on_engine(
                lambda eng: eng.begin_kv_import(list(prompt_ids),
                                                len(blocks))
            )
            if got is None:
                return
            local, n_full = got
            async with aiohttp.ClientSession() as s:
                async with s.post(
                    f"{host}/kv/export",
                    json={"blocks": blocks[:n_full], "stream": True},
                    timeout=aiohttp.ClientTimeout(total=120),
                ) as resp:
                    if resp.status != 200:
                        raise RuntimeError(f"export HTTP {resp.status}")
                    shape = tuple(
                        int(x) for x in resp.headers["X-KV-Shape"].split(",")
                    )
                    dtype = resp.headers["X-KV-Dtype"]
                    group = int(resp.headers["X-KV-Group-Layers"])
                    await consume_frames(
                        resp.content, self.async_engine.run_on_engine,
                        local, shape, dtype, group,
                    )
            cached = await self.async_engine.run_on_engine(
                lambda eng: eng.finish_kv_import(list(prompt_ids), local)
            )
            local = None  # committed
            if cached:
                body.setdefault("_kv_imported_tokens", cached)
        except Exception as e:
            # transfer is best-effort; decode recomputes on miss
            import logging

            logging.getLogger(__name__).warning("kv import failed: %s", e)
            if local is not None:
                await self.async_engine.run_on_engine(
                    lambda eng: eng.abort_kv_import(local)
                )

    # -- streamed P→D handoff, receive side (disaggregated decode) ----------
    def _sweep_kv_transfers(self) -> None:
        """Free KV blocks held by transfers whose decode hop never came
        (router died between push and continuation): past the TTL the
        blocks go back to the pool — a leaked transfer must never pin
        pages forever. Runs lazily on every /kv/recv and attach."""
        ttl = getattr(self.config, "kv_transfer_ttl", 120.0)
        now = time.monotonic()
        for tid in list(self._kv_transfers):
            st = self._kv_transfers.get(tid)
            if st is None or now - st["created"] <= ttl:
                continue
            self._kv_transfers.pop(tid, None)
            blocks = st["blocks"]
            _log.warning("kv transfer %s expired unattached; freeing "
                         "%d blocks", tid, len(blocks))
            task = asyncio.ensure_future(self.async_engine.run_on_engine(
                lambda eng, b=blocks: eng.scheduler.allocator.free_blocks(b)
            ))
            self._bg_tasks.add(task)
            task.add_done_callback(self._bg_tasks.discard)
            task.add_done_callback(_log_bg_task_failure)

    async def kv_recv(self, request: web.Request) -> web.Response:
        """Receiver for a PUSHED prefill→decode transfer (the body is the
        kv_transfer.py frame stream: one JSON meta prologue frame, then
        CRC-tailed layer-group frames). Blocks land straight into free
        pages of the paged pool; the later decode hop attaches them via
        ``kv_transfer_params.transfer_id`` and splices the sequence in
        decode-ready. A digest mismatch or dropped connection answers 409
        {"resume_layer": n} so the producer resends only the unlanded
        groups."""
        import zlib

        from production_stack_tpu.engine.kv_transfer import (
            FRAME_CRC,
            FRAME_HEADER,
            FrameDigestError,
            consume_frames,
        )

        self._sweep_kv_transfers()
        tid = request.headers.get("X-KV-Transfer-Id") or ""
        try:
            shape = tuple(
                int(x) for x in request.headers["X-KV-Shape"].split(","))
            dtype = request.headers["X-KV-Dtype"]
            group = max(1, int(request.headers["X-KV-Group-Layers"]))
            start_layer = int(request.headers.get("X-KV-Start-Layer", "0"))
        except (KeyError, ValueError):
            return web.json_response(
                {"error": {"message": "missing/invalid X-KV-* headers"}},
                status=400,
            )
        if not tid or len(shape) != 5:
            return web.json_response(
                {"error": {"message": "X-KV-Transfer-Id and a 5-dim "
                           "X-KV-Shape are required"}}, status=400)
        state = self._kv_transfers.get(tid)
        resume_at = state["layers_done"] if state else 0

        content = request.content
        try:  # meta prologue frame (transfer id, prompt ids, first token)
            head = await content.readexactly(FRAME_HEADER.size)
            (nbytes,) = FRAME_HEADER.unpack(head)
            payload = await content.readexactly(nbytes)
            (crc,) = FRAME_CRC.unpack(
                await content.readexactly(FRAME_CRC.size))
            if zlib.crc32(payload) != crc:
                return web.json_response({"resume_layer": resume_at},
                                         status=409)
            meta = json.loads(payload)
        except (asyncio.IncompleteReadError, ValueError):
            return web.json_response({"resume_layer": resume_at}, status=409)

        if state is None:
            if start_layer != 0:
                # resume for a transfer we never saw (e.g. swept): restart
                return web.json_response({"resume_layer": 0}, status=409)
            blocks = await self.async_engine.run_on_engine(
                lambda eng: eng.begin_kv_receive(int(shape[1]))
            )
            if blocks is None:
                return web.json_response(
                    {"error": {"message": "KV pool cannot hold the "
                               "transfer right now"}}, status=503)
            state = {"blocks": blocks, "layers_done": 0, "meta": meta,
                     "created": time.monotonic(), "ready": False}
            self._kv_transfers[tid] = state
        elif start_layer != state["layers_done"]:
            # the producer's idea of progress disagrees with ours
            # (connection-error retry restarts at 0): re-anchor it
            return web.json_response(
                {"resume_layer": state["layers_done"]}, status=409)

        def on_group(lo: int, n: int) -> None:
            state["layers_done"] = lo + n

        t0 = time.monotonic()
        try:
            landed = await consume_frames(
                content, self.async_engine.run_on_engine, state["blocks"],
                shape, dtype, group, start_layer=start_layer,
                on_group=on_group,
            )
        except FrameDigestError:
            return web.json_response(
                {"resume_layer": state["layers_done"]}, status=409)
        except (asyncio.IncompleteReadError, ValueError,
                ConnectionResetError):
            # dropped mid-body / short stream: keep the landed groups for
            # the retry (the TTL sweep reclaims them if none comes)
            return web.json_response(
                {"resume_layer": state["layers_done"]}, status=409)
        state["ready"] = True
        import numpy as np

        itemsize = 2 if dtype == "bfloat16" else np.dtype(dtype).itemsize
        per_layer = itemsize
        for d in shape[1:]:
            per_layer *= int(d)
        self.metrics.observe_transfer("recv", landed * per_layer,
                                      time.monotonic() - t0)
        return web.json_response({"ok": True, "transfer_id": tid,
                                  "layers": int(shape[0])})

    async def _push_kv_blocks(self, push_url: str, transfer_id: str,
                              blocks: list, prompt_ids: list,
                              first_token: int) -> bool:
        """Producer side of the streamed handoff: pin the finished
        prefill's blocks (serving steps interleave with the gathers — an
        eviction mid-push would tear the transfer) and stream them to the
        decode engine's /kv/recv. Best-effort: on failure the decode hop
        falls back to pulling /kv/export or plain re-prefill."""
        import aiohttp

        from production_stack_tpu.engine.kv_transfer import push_kv

        cfg = self.config
        shape = cfg.model.kv_pool_shape(len(blocks), cfg.cache.block_size)
        dtype = str(cfg.model.dtype)
        meta = {"transfer_id": transfer_id,
                "prompt_token_ids": [int(t) for t in prompt_ids],
                "first_token": int(first_token)}
        t0 = time.monotonic()
        await self.async_engine.run_on_engine(
            lambda eng: eng.scheduler.allocator.pin_blocks(blocks)
        )
        try:
            async with aiohttp.ClientSession() as s:
                await push_kv(
                    s, push_url, self.async_engine.run_on_engine, blocks,
                    shape, dtype, meta,
                    group=getattr(cfg, "kv_transfer_group_layers", 0) or None,
                    window=getattr(cfg, "kv_transfer_window", 2),
                    retries=getattr(cfg, "kv_transfer_retries", 3),
                    timeout=getattr(cfg, "kv_transfer_ttl", 120.0),
                )
        except Exception as e:
            _log.warning("kv push %s -> %s failed: %s",
                         transfer_id, push_url, e)
            return False
        finally:
            await self.async_engine.run_on_engine(
                lambda eng: eng.scheduler.allocator.free_blocks(blocks)
            )
        import numpy as np

        itemsize = 2 if dtype == "bfloat16" else np.dtype(dtype).itemsize
        nbytes = itemsize
        for d in shape:
            nbytes *= int(d)
        self.metrics.observe_transfer("push", nbytes,
                                      time.monotonic() - t0)
        return True

    async def detokenize(self, request: web.Request) -> web.Response:
        body = await request.json()
        return web.json_response({"prompt": self.engine.tokenizer.decode(body.get("tokens") or [])})

    async def debug_faults(self, request: web.Request) -> web.Response:
        """Flip fault injection on a LIVE engine (resilience drills,
        tutorials/22-fault-injection.md) — no pod restart needed.

        Query params mirror the --fault-injection spec string:
        ``?error_rate=0.5&latency_ms=100&drop_rate=0.1&seed=7``;
        ``?off=1`` clears. /debug/* is outside the faulted /v1/* surface,
        so the toggle itself never faults."""
        from production_stack_tpu.testing.faults import FaultSpec

        q = request.rel_url.query
        try:
            off = q.get("off")
            if off is not None:
                if off.lower() not in ("1", "true"):
                    raise ValueError("off must be 1 or true")
                self.faults.set(None)
            else:
                spec = ",".join(f"{k}={v}" for k, v in q.items())
                self.faults.set(FaultSpec.parse(spec))
        except (TypeError, ValueError) as e:
            return web.json_response(
                {"error": {"message": str(e)}}, status=400
            )
        s = self.faults.spec
        body = {"active": s is not None}
        if s is not None:
            body.update(error_rate=s.error_rate, latency_ms=s.latency_ms,
                        drop_rate=s.drop_rate, stall_ms=s.stall_ms,
                        stream_abort_rate=s.stream_abort_rate,
                        stream_abort_after_ms=s.stream_abort_after_ms,
                        hang_after_ms=s.hang_after_ms)
        return web.json_response(body)

    # -- profiling ------------------------------------------------------------
    async def profile(self, request: web.Request) -> web.Response:
        """Capture a JAX profiler trace (XPlane protos + trace-viewer JSON,
        the TensorBoard-loadable format) for ``duration_ms`` while serving
        continues, and return it as a tar.gz. This is the TPU equivalent of
        vLLM's torch-profiler start/stop endpoints (SURVEY.md §5.1): the
        trace shows per-kernel device time, HBM traffic, and host gaps —
        the evidence behind docs/roofline.md.

        The trace keeps the runtime's own host events and the engine's
        ``engine_step`` / ``step.<phase>`` annotations (engine/tracing.py).
        The per-call Python hooks are off unless the body asks for them
        with ``"python_tracer": true``: they slow a host-bound step loop
        enough to change what the trace shows."""
        import io
        import shutil
        import tarfile
        import tempfile

        import jax

        try:
            body = await request.json()
        except Exception:
            body = {}
        duration_ms = min(int(body.get("duration_ms") or 2000), 60_000)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 1 if body.get("python_tracer") else 0
        if getattr(self, "_profiling", False):
            return web.json_response(
                {"error": {"message": "a profile capture is already running"}},
                status=409,
            )
        self._profiling = True
        tmp = tempfile.mkdtemp(prefix="jaxprof-")
        started = False
        try:
            jax.profiler.start_trace(tmp, profiler_options=options)
            started = True
            await asyncio.sleep(duration_ms / 1000.0)
            # stop + tar off the event loop: a trace under load is large
            # and serialising it inline would stall every stream

            def _finish() -> bytes:
                jax.profiler.stop_trace()
                buf = io.BytesIO()
                with tarfile.open(fileobj=buf, mode="w:gz") as tar:
                    tar.add(tmp, arcname="trace")
                return buf.getvalue()

            body_bytes = await asyncio.get_running_loop().run_in_executor(
                None, _finish
            )
            started = False
            return web.Response(
                body=body_bytes,
                content_type="application/gzip",
                headers={"Content-Disposition":
                         'attachment; filename="jax-trace.tar.gz"'},
            )
        except Exception as e:
            return web.json_response(
                {"error": {"message": f"profile capture failed: {e}"}},
                status=500,
            )
        finally:
            if started:
                # cancellation (client disconnect) skipped _finish: the
                # profiler must not be left running or the endpoint is
                # dead until restart
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    _log.debug("profiler stop_trace cleanup failed",
                               exc_info=True)
            self._profiling = False
            shutil.rmtree(tmp, ignore_errors=True)

    async def debug_perf(self, request: web.Request) -> web.Response:
        """Goodput-accounting snapshot (engine/perf_accounting.py): live
        MFU / HBM-bandwidth utilization, phase throughput, HBM occupancy,
        the compile-event log, and the speculative-decoding acceptance
        picture — the always-on counterpart to the profiler endpoints
        above."""
        perf = getattr(self.engine, "perf", None)
        kv_block = {
            "role": getattr(self.config, "role", "unified"),
            "pending_transfers": len(self._kv_transfers),
            "transfers": self.metrics.transfer_totals,
        }
        # tiered-KV snapshot (hit/demote/promote counters, byte traffic,
        # prefetch latency + overlap) — the /debug/fleet join and stacktop
        # read it from here
        tier_block = None
        if (getattr(self.engine, "host_kv", None) is not None
                or getattr(self.engine, "remote_kv", None) is not None):
            tier_block = self.engine.tier_stats()
        # the engine thread's step clock: seconds by step kind and loop
        # phase (wall and on-CPU), steps by kind, idle seconds — the
        # numbers behind vllm:engine_host_seconds_total and its siblings
        step_phases = self.engine.clock.snapshot()
        # the steps found slow against the steps like them before them
        # (vllm:engine_slow_step_seconds_total) and the last of them;
        # a request's time to first token in parts, seconds and requests
        # by part (vllm:request_ttft_part_seconds_total, ..._parts_total);
        # the event loop's heartbeat lag by the engine thread's phase
        # (vllm:server_loop_lag_seconds_total, vllm:server_loop_ticks_total)
        request_path = {"slow_steps": self.engine.clock.slow_snapshot(),
                        "ttft_parts": self.ttft_parts.snapshot(),
                        "loop_lag": self.loop_lag.snapshot()}
        # decode dispatches, those launched from inputs prepared under
        # the dispatch before and, of those, the ones queued a lead ahead
        # of its landing (vllm:decode_dispatches_total,
        # vllm:decode_prepared_launches_total,
        # vllm:decode_ahead_launches_total), with the lead as it came out:
        # from such a launch to the landing it went ahead of. What the
        # lead cost: of the requests taken in, those that reached the
        # intake before a landing with the next program queued
        # (vllm:arrivals_behind_queued_decode_total over
        # vllm:engine_intake_requests_total); and the ragged landings at
        # which an arrival kept a decode program from being launched
        # (vllm:ragged_landing_arrivals_total)
        eng = self.engine
        step_loop = {
            "decode_dispatches": eng.decode_dispatches,
            "decode_prepared_launches": eng.decode_prepared_launches,
            "decode_ahead_launches": eng.decode_ahead_launches,
            "decode_ahead_lead_seconds": {
                "mean": (eng.decode_ahead_lead_seconds
                         / max(1, eng.decode_ahead_launches)),
                "max": eng.decode_ahead_lead_max_seconds},
            "intake_requests": eng.intake_requests,
            "arrivals_behind_queued_decode":
                eng.arrivals_behind_queued_decode,
            "ragged_landing_arrivals": eng.ragged_landing_arrivals}
        # the ragged attention kernel's walks, and those on its narrow
        # row block (vllm:ragged_attn_walks_total, ..._narrow_walks_total)
        walks = {"ragged_dispatches": self.engine.ragged_dispatches,
                 # those that ran at a narrow stream width
                 # (vllm:ragged_narrow_dispatches_total)
                 "ragged_narrow_dispatches":
                     self.engine.ragged_narrow_dispatches,
                 "ragged_attn_walks": self.engine.ragged_attn_walks,
                 "ragged_attn_narrow_walks":
                     self.engine.ragged_attn_narrow_walks,
                 # the context windows those walks stream, and those on
                 # the kernel's interior body (vllm:ragged_attn_windows_
                 # total, vllm:ragged_attn_interior_windows_total)
                 "ragged_attn_windows": self.engine.ragged_attn_windows,
                 "ragged_attn_interior_windows":
                     self.engine.ragged_attn_interior_windows,
                 # the decode dispatches' attention calls, and those the
                 # decode kernel scored from the slab as stored (vllm:
                 # decode_attn_calls_total, ..._slab_calls_total)
                 "decode_attn_calls": self.engine.decode_attn_calls,
                 "decode_attn_slab_calls":
                     self.engine.decode_attn_slab_calls}
        moe = getattr(self.engine.runner, "moe", None)
        if moe is not None:
            # what the MoE block routed, and the layer-steps whose grouped
            # matmuls ran the Pallas kernel (vllm:moe_*_total;
            # engine/tracing.py MoeCounters)
            walks["moe"] = moe.snapshot()
        if perf is None:
            return web.json_response({"enabled": False,
                                      "kv_transfer": kv_block,
                                      "kv_tier": tier_block,
                                      "step_phases": step_phases,
                                      "step_loop": step_loop,
                                      **request_path, **walks,
                                      "tenants": self.engine.tenant_stats()})
        snap = perf.snapshot()
        snap["step_phases"] = step_phases
        snap["step_loop"] = step_loop
        snap.update(request_path)
        snap.update(walks)
        eng = self.engine
        drafted = getattr(eng, "spec_drafted", 0)
        steps = getattr(eng, "spec_steps", 0)
        snap["speculative"] = {
            "enabled": getattr(eng, "_spec", None) is not None,
            "draft_tokens": drafted,
            "accepted_tokens": getattr(eng, "spec_accepted", 0),
            "acceptance_rate": (
                getattr(eng, "spec_accepted", 0) / drafted if drafted else 0.0
            ),
            "tokens_per_step": (
                getattr(eng, "spec_step_tokens", 0) / steps if steps else 0.0
            ),
        }
        snap["kv_transfer"] = kv_block
        snap["kv_tier"] = tier_block
        recurrent = self.engine.recurrent_stats()
        if recurrent:
            # a hybrid stack's second kind of cache: per-slot state bytes
            # beside the pool's, what the recurrent layers ran, and that
            # prefix lookups are answered as misses
            snap["recurrent_state"] = {
                **recurrent, "prefix_cache": "bypassed",
                "kv_pool_bytes": (self.engine.runner.num_blocks
                                  * self.engine._kv_bytes_per_block)}
        window = getattr(self.engine, "window_counters", None)
        if window is not None:
            # a window that binds: the window layers' pool of blocks beside
            # the other one, what their walks stream and what they would
            # without a window, the cross-attention layers' calls
            model = self.engine.config.model
            snap["window_pool"] = {
                **window.snapshot(self.engine.scheduler),
                "sliding_window": model.sliding_window,
                "window_kv_bytes_per_token": model.window_kv_bytes_per_token,
                "window_pool_bytes": (
                    self.engine.runner.window_blocks
                    * self.engine.config.cache.block_size
                    * model.window_kv_bytes_per_token)}
        snap["tenants"] = self.engine.tenant_stats()
        snap["fingerprint"] = self._perf_fingerprint()
        # the start in parts (engine/tracing.py StartClock): the spans as
        # recorded, and seconds by phase (vllm:engine_start_seconds)
        snap["start"] = {
            **self.start.snapshot(),
            "tokenizer_loader": self.engine.tokenizer.loader}
        snap["startup_seconds"] = {
            phase: round(sec, 2)
            for phase, sec in snap["start"]["seconds"].items()}
        return web.json_response(snap)

    async def debug_tenants(self, request: web.Request) -> web.Response:
        """Per-tenant attribution snapshot: token/chip-second/KV/queue
        accounting folded to the configured top-K (+"other"), plus ledger
        health. The router's /debug/fleet join and stacktop --tenants read
        this; the same data backs the vllm:tenant_* metric families."""
        block = dict(self.engine.tenant_stats())
        block["model"] = self.model_name
        if self.usage_ledger is not None:
            block["ledger"] = self.usage_ledger.stats()
        return web.json_response(block)

    async def debug_canary(self, request: web.Request) -> web.Response:
        """Golden-capture surface for the correctness canary plane
        (docs/observability.md "Correctness canaries"): runs the pinned
        probe set through the normal admission path — greedy, logprobs
        on, attributed to the reserved ``_canary`` tenant — and returns
        the resulting golden-record documents. ``tools/canaryctl.py
        record`` captures this from a trusted engine to seed the
        router's golden store. No new jit signature: the probes use the
        same sampling/compute_logprobs path as any logprobs-on
        completions request. ``?tolerance=`` stamps a per-record
        L-infinity band for quantized fleets (default 0.0: bit-exact)."""
        from production_stack_tpu.canary_golden import (
            DEFAULT_PROBES,
            record_from_response,
        )
        from production_stack_tpu.tenancy import CANARY_TENANT

        try:
            tolerance = float(request.query.get("tolerance", 0.0))
        except ValueError:
            return web.json_response(
                {"error": {"message": "tolerance must be a float",
                           "type": "invalid_request_error"}},
                status=400,
            )
        tk = self.engine.tokenizer
        records, errors = [], []
        for probe in DEFAULT_PROBES:
            rid = f"canary-{probe.id}-{uuid.uuid4().hex[:8]}"
            sampling = SamplingParams(
                max_tokens=probe.max_tokens, temperature=0.0,
                logprobs=probe.top_k,
            )
            prompt_ids = tk.encode(probe.prompt)
            try:
                gens = await self.async_engine.admit_batch(
                    [(rid, prompt_ids, sampling,
                      self.lora.slot_of(self.model_name), CANARY_TENANT)])
                token_ids: list[int] = []
                lps: list = []
                async for out in gens[0]:
                    token_ids.extend(out.new_token_ids)
                    if out.new_logprobs:
                        lps.extend(out.new_logprobs)
            except Exception as e:  # a sick engine still answers canaryctl
                errors.append({"probe": probe.id, "error": str(e)})
                continue
            payload = {"choices": [{
                "text": tk.decode(token_ids),
                "logprobs": _fmt_completion_logprobs(
                    tk, token_ids, lps, probe.top_k),
            }]}
            try:
                rec = record_from_response(
                    self.model_name, probe, payload, tolerance=tolerance,
                    source=f"engine:{self.model_name}", created=time.time(),
                )
            except ValueError as e:
                errors.append({"probe": probe.id, "error": str(e)})
                continue
            records.append(rec.to_dict())
        return web.json_response({
            "model": self.model_name,
            "records": records,
            "errors": errors,
        })

    async def memory_profile(self, request: web.Request) -> web.Response:
        """Device memory profile (pprof proto) — what holds HBM right now."""
        import jax

        try:
            data = jax.profiler.device_memory_profile()
        except Exception as e:
            return web.json_response(
                {"error": {"message": f"memory profile failed: {e}"}},
                status=500,
            )
        return web.Response(
            body=data, content_type="application/octet-stream",
            headers={"Content-Disposition":
                     'attachment; filename="memory.pprof"'},
        )

    # -- anomaly diagnostics (engine/diagnostics.py) --------------------------
    def _collect_perf(self) -> dict:
        perf = getattr(self.engine, "perf", None)
        return perf.snapshot() if perf is not None else {"enabled": False}

    def _collect_flight_recorder(self) -> dict:
        return {"recorder": self.flight_recorder.stats(),
                "requests": self.flight_recorder.snapshot()}

    def _collect_scheduler(self) -> dict:
        stats = self.engine.stats()
        perf = stats.get("perf")
        if isinstance(perf, dict):
            # stats_fields() keys compile_counts by (kind, bucket) tuples
            # for the metrics scraper; JSON needs the "kind:bucket" form
            counts = perf.get("compile_counts")
            if isinstance(counts, dict):
                perf = dict(perf)
                perf["compile_counts"] = {
                    f"{k}:{b}": n for (k, b), n in sorted(counts.items())}
                stats["perf"] = perf
        return stats

    def _collect_compile_tail(self) -> list:
        perf = getattr(self.engine, "perf", None)
        if perf is None:
            return []
        return perf.snapshot()["compile"]["recent"]

    def _collect_device_memory(self) -> bytes:
        import jax

        return jax.profiler.device_memory_profile()

    def _diag_profile(self, trace_dir: str) -> bool:
        """Short jax trace for a diagnostic bundle. Runs on the capture
        thread (never the event loop); shares the /debug/profile
        single-flight flag so the two capture paths never fight over the
        process-global profiler. Returns False when the profiler is busy
        — the bundle records that instead of failing."""
        import jax

        if getattr(self, "_profiling", False):
            return False
        self._profiling = True
        try:
            seconds = min(self.diagnostics.config.profile_seconds, 10.0)
            jax.profiler.start_trace(trace_dir)
            try:
                time.sleep(seconds)
            finally:
                jax.profiler.stop_trace()
            return True
        finally:
            self._profiling = False

    async def diagnostics_index(self, request: web.Request) -> web.Response:
        """Bundle archive index: what was captured, why, how big, plus
        the anomaly event tail (including captures skipped by the
        cooldown / single-flight gates)."""
        return web.json_response(self.diagnostics.index())

    async def diagnostics_bundle(self, request: web.Request) -> web.Response:
        bundle_id = request.match_info["bundle_id"]
        data = await asyncio.get_running_loop().run_in_executor(
            None, self.diagnostics.tar_bundle, bundle_id)
        if data is None:
            return web.json_response(
                {"error": {"message": f"no diagnostic bundle {bundle_id!r}"}},
                status=404,
            )
        return web.Response(
            body=data, content_type="application/gzip",
            headers={"Content-Disposition":
                     f'attachment; filename="{bundle_id}.tar.gz"'},
        )

    async def diagnostics_capture(self, request: web.Request) -> web.Response:
        """Correlated capture: the router's incident fan-out POSTs here
        with {"trigger", "incident", "detail"} so the fleet's bundles
        share an incident id. Runs the capture in an executor and
        answers only once the bundle is on disk."""
        if not self.diagnostics.config.enabled:
            return web.json_response(
                {"captured": False, "reason": "diagnostics disabled"},
                status=400,
            )
        try:
            body = await request.json()
        except Exception:
            body = {}
        trigger = str(body.get("trigger") or "manual")
        detail = dict(body.get("detail") or {})
        if body.get("incident"):
            detail["incident"] = body["incident"]
        bundle_id = await asyncio.get_running_loop().run_in_executor(
            None,
            lambda: self.diagnostics.trigger(trigger, detail,
                                             force=True, sync=True))
        if bundle_id is None:
            return web.json_response(
                {"captured": False, "reason": "a capture is in flight"},
                status=409,
            )
        return web.json_response({"captured": True, "bundle": bundle_id})

    # -- sleep family ---------------------------------------------------------
    async def sleep(self, request: web.Request) -> web.Response:
        level = int(request.query.get("level", 1))
        try:
            await self.async_engine.sleep(level)
        except RuntimeError as e:
            self.async_engine.paused = False
            return web.json_response({"error": {"message": str(e)}}, status=409)
        return web.json_response({"status": "sleeping", "level": level})

    async def wake_up(self, request: web.Request) -> web.Response:
        await self.async_engine.wake_up()
        return web.json_response({"status": "awake"})

    async def is_sleeping(self, request: web.Request) -> web.Response:
        return web.json_response({"is_sleeping": self.async_engine.is_sleeping})

    # -- completions -----------------------------------------------------------
    def _render_chat(self, messages: list[dict]) -> str:
        prompt = self.engine.tokenizer.render_chat(messages)
        if prompt is not None:
            return prompt
        parts = [f"<|{m.get('role', 'user')}|>\n{m.get('content', '')}" for m in messages]
        return "\n".join(parts) + "\n<|assistant|>\n"

    async def chat_completions(self, request: web.Request) -> web.StreamResponse:
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": {"message": "invalid JSON body"}}, status=400)
        if "messages" not in body:
            return web.json_response(
                {"error": {"message": "'messages' is required"}}, status=400
            )
        prompt = self._render_chat(body["messages"])
        return await self._run(request, body, [prompt], chat=True)

    async def completions(self, request: web.Request) -> web.StreamResponse:
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": {"message": "invalid JSON body"}}, status=400)
        prompt = body.get("prompt")
        if prompt is None:
            return web.json_response(
                {"error": {"message": "'prompt' is required"}}, status=400
            )
        # OpenAI accepts: str | [str, ...] | [int, ...] (one tokenized
        # prompt) | [[int, ...], ...] (a batch of tokenized prompts). Batched
        # prompts fan out into concurrent engine requests (one choice per
        # prompt x n).
        def _is_token_list(p):
            return (isinstance(p, list) and p
                    and all(isinstance(t, int) for t in p))

        if isinstance(prompt, str):
            prompts = [prompt]
        elif _is_token_list(prompt):
            prompts = [prompt]
        elif (isinstance(prompt, list) and prompt
              and all(isinstance(p, str) or _is_token_list(p)
                      for p in prompt)):
            prompts = prompt
        else:
            return web.json_response(
                {"error": {"message": "invalid 'prompt': expected string, "
                           "token list, or batch thereof",
                           "type": "invalid_request_error"}},
                status=400,
            )
        return await self._run(request, body, prompts, chat=False)

    async def _run(self, request: web.Request, body: dict, prompts: list,
                   chat: bool) -> web.StreamResponse:
        """Observability shell around the request lifecycle: joins the
        router's trace via the propagated W3C traceparent (child SERVER
        span carrying queue/prefill/decode stage timing), opens a flight-
        recorder record keyed by the propagated x-request-id, and logs a
        completion line per request."""
        rid = f"{'chatcmpl' if chat else 'cmpl'}-{uuid.uuid4().hex}"
        client_rid = request.headers.get("x-request-id") or rid
        model = str(body.get("model", self.model_name))
        inbound_ctx = etracing.extract_context(request.headers)
        span_cm = etracing.request_span(
            f"engine {request.path}",
            context=inbound_ctx,
            kind="server",
            attributes={"request.id": rid, "client.request.id": client_rid,
                        "http.target": request.path, "model": model},
        )
        # tenant identity for attribution (tenancy.resolve_tenant):
        # x-tenant-id header (the router stamps the resolved identity
        # here) > OpenAI `user` body field > API-key hash > "anonymous".
        tenant = resolve_tenant(request.headers, body)
        request["tenant"] = tenant
        rec = self.flight_recorder.begin(
            request_id=rid, client_request_id=client_rid,
            endpoint=request.path, model=model, tenant=tenant,
            streaming=bool(body.get("stream", False)),
            trace_id=None, outcome=None, status=None,
            num_prompt_tokens=0, num_output_tokens=0,
            steps={"received": self.engine.clock.step_num},
        )
        # the wall-clock instant the router forwarded the request, beside
        # `received_unix`: their difference is the hop and the wait for
        # this handler (exact on one host; across nodes it carries the
        # nodes' clock skew)
        sent = request.headers.get("x-router-sent-unix")
        if sent is not None:
            try:
                rec["router_sent_unix"] = float(sent)
            except ValueError:
                pass
        self._inflight[rid] = rec
        status = 500
        try:
            with span_cm as span:
                # current-span id when the SDK records spans; the router's
                # propagated id in API-only (propagation-only) mode
                rec["trace_id"] = (etracing.trace_id_hex()
                                   or etracing.trace_id_hex(inbound_ctx))
                try:
                    resp = await self._run_inner(
                        request, body, prompts, chat, rid)
                    status = resp.status
                    # streamed responses set this at prepare time; echo on
                    # buffered/error responses too so direct clients can
                    # correlate with logs and /debug/requests
                    if not resp.prepared and \
                            "x-request-id" not in resp.headers:
                        resp.headers["x-request-id"] = client_rid
                finally:
                    self._finalize_span(span, rec, status,
                                        self._note_ttft_parts(rec))
                return resp
        except asyncio.CancelledError:
            if rec.get("outcome") is None:
                rec["outcome"] = "client_disconnect"
            raise
        finally:
            self._inflight.pop(rid, None)
            if rec.get("outcome") is None:
                rec["outcome"] = ("completed" if status < 400
                                  else "deadline_exceeded" if status == 504
                                  else "rejected")
            rec["status"] = status
            self.flight_recorder.finish(rec)
            tl = rec["timeline"]
            _log.info(
                "request %s x-request-id=%s status=%s outcome=%s "
                "prompt_tokens=%d output_tokens=%d e2e=%.3fs",
                rid, client_rid, status, rec["outcome"],
                rec["num_prompt_tokens"], rec["num_output_tokens"],
                tl["finished"] - tl["received"],
            )

    def _note_ttft_parts(self, rec: dict) -> dict:
        """A request's time to first token in parts (tracing.ttft_parts),
        from the stamps its record holds by now: kept in the record and
        added to the totals if it got a first token. The one place the
        record, /debug/perf, /metrics and the span read them from."""
        parts = etracing.ttft_parts(rec["timeline"])
        if "first_token" in rec["timeline"]:
            rec["ttft_parts"] = parts
            self.ttft_parts.add(parts)
        return parts

    def _finalize_span(self, span, rec: dict, status: int,
                       parts: dict) -> None:
        """Stamp per-stage durations (``parts``: the record's time to
        first token in parts) onto the engine SERVER span: queue is what
        lies before admission, prefill what lies between it and the first
        token."""
        if span is None:
            return
        tl = rec["timeline"]
        span.set_attribute("http.status_code", status)
        for part, seconds in parts.items():
            span.set_attribute(f"stage.{part}_s", seconds)
        if "queue_wait" in parts:
            span.set_attribute("stage.queue_s", sum(
                parts[p] for p in etracing.QUEUE_PARTS if p in parts))
            span.add_event("admitted")
        if "prefill_steps" in parts:
            span.set_attribute("stage.prefill_s", sum(
                parts[p] for p in etracing.PREFILL_PARTS if p in parts))
            span.add_event("first_token")
        if "last_token" in tl and "first_token" in tl:
            span.set_attribute("stage.decode_s",
                               tl["last_token"] - tl["first_token"])
        span.set_attribute("tokens.prompt", rec["num_prompt_tokens"])
        span.set_attribute("tokens.output", rec["num_output_tokens"])

    def _observe_finished(self, root_rid: str, out) -> None:
        """Per-choice finished output: feed the per-stage histograms and
        merge the sequence's lifecycle stamps into the request's flight
        record (min across choices for admission/first-token, max for
        finish)."""
        self.metrics.observe_stages(out)
        rec = self._inflight.get(root_rid)
        if rec is None:
            return
        # beside each stamp of the timeline, under the same key in `steps`:
        # the engine step (`engine_step` annotation of a profiler trace)
        # that took it
        at_step = out.steps or {}
        first = rec["timeline"].get("first_token")
        if out.first_token_time is not None and (
                first is None or out.first_token_time < first):
            # of the choice whose first token came first
            rec["intake_after"] = out.arrival_after
            rec["prefill_dispatches"] = out.prefill_dispatches
        for key, val, pick in (("enqueued", out.enqueue_time, min),
                               ("arrival", out.arrival_time, min),
                               ("admitted", out.admit_time, min),
                               ("first_launch", out.first_launch_time, min),
                               ("first_token", out.first_token_time, min),
                               ("last_token", out.finish_time, max)):
            for into, v in ((rec["timeline"], val),
                            (rec.setdefault("steps", {}), at_step.get(key))):
                if v is not None:
                    into[key] = v if key not in into else pick(into[key], v)
        rec["num_prompt_tokens"] += out.num_prompt_tokens
        rec["num_output_tokens"] += out.num_output_tokens
        if self.usage_ledger is not None:
            stamps = {}
            if out.admit_time is not None and out.arrival_time is not None:
                stamps["queue_s"] = round(
                    out.admit_time - out.arrival_time, 6)
            if (out.first_token_time is not None
                    and out.admit_time is not None):
                stamps["prefill_s"] = round(
                    out.first_token_time - out.admit_time, 6)
            if (out.finish_time is not None
                    and out.first_token_time is not None):
                stamps["decode_s"] = round(
                    out.finish_time - out.first_token_time, 6)
            self.usage_ledger.append({
                "ts": time.time(),
                "tenant": out.tenant,
                "model": rec.get("model", self.model_name),
                "request_id": out.request_id,
                "client_request_id": rec.get("client_request_id"),
                "prompt_tokens": out.num_prompt_tokens,
                "output_tokens": out.num_output_tokens,
                "cached_tokens": out.num_cached_tokens,
                "chip_seconds": round(out.chip_seconds, 9),
                "finish_reason": out.finish_reason,
                **stamps,
            })

    async def _run_inner(self, request: web.Request, body: dict,
                         prompts: list, chat: bool,
                         rid: str) -> web.StreamResponse:
        try:
            sampling = _sampling_from_body(body)
            lp_n = _parse_logprobs(body, chat)
            if lp_n is not None:
                sampling = dataclasses.replace(sampling, logprobs=lp_n)
            # validate token controls HERE (the engine recomputes them in
            # add_request, after this handler has already committed to a
            # stream) so bad ids/overflow become a 400, not a mid-stream 500
            make_token_controls(sampling, self.config.model.vocab_size)
        except (TypeError, ValueError) as e:
            return web.json_response(
                {"error": {"message": f"invalid sampling parameter: {e}",
                           "type": "invalid_request_error"}},
                status=400,
            )
        g_re = body.get("guided_regex")
        g_js = body.get("guided_json")
        if g_re is not None or g_js is not None:
            err = None
            if g_re is not None and g_js is not None:
                err = "guided_regex and guided_json are mutually exclusive"
            elif body.get("guided_choice") is not None:
                err = "guided_choice cannot combine with other guidance"
            elif g_re is not None and not isinstance(g_re, str):
                err = "guided_regex must be a string"
            else:
                try:  # validate the grammar NOW — a 400, not a mid-stream 500
                    from production_stack_tpu.engine.grammar import (
                        compile_regex,
                        schema_to_regex,
                    )

                    pat = g_re if g_re is not None else schema_to_regex(g_js)
                    compile_regex(
                        pat, max_states=self.config.max_grammar_states
                    )
                except (ValueError, IndexError, KeyError, TypeError) as e:
                    # RegexError subclasses ValueError; the extra types
                    # keep any residual parser edge case a 400, never a 500
                    err = f"invalid guided grammar: {e}"
            if err is not None:
                return web.json_response(
                    {"error": {"message": err,
                               "type": "invalid_request_error"}},
                    status=400,
                )
            sampling = dataclasses.replace(
                sampling, guided_regex=g_re, guided_json=g_js
            )
        if sampling.n < 1 or sampling.n * len(prompts) > MAX_CHOICES:
            return web.json_response(
                {"error": {"message":
                           f"n x prompt batch size must be in [1, {MAX_CHOICES}]",
                           "type": "invalid_request_error"}},
                status=400,
            )
        tk = self.engine.tokenizer
        prompt_ids_list = [
            tk.encode(p) if isinstance(p, str) else list(p) for p in prompts
        ]
        created = int(time.time())
        model = body.get("model", self.model_name)
        stream = bool(body.get("stream", False))
        t_start = time.monotonic()
        deadline = _parse_deadline(request.headers)
        if deadline is not None and deadline <= time.time():
            # expired before admission: refuse without touching the
            # scheduler — cheapest possible shed
            return web.json_response(
                {"error": {"message": "x-request-deadline already expired",
                           "type": "timeout_error"}},
                status=504,
            )

        for prompt_ids in prompt_ids_list:
            if len(prompt_ids) > self.config.model.max_model_len - 1:
                return web.json_response(
                    {"error": {"message": "prompt too long", "type": "invalid_request_error"}},
                    status=400,
                )

        echo = bool(body.get("echo")) and not chat
        if echo:
            err = None
            if stream:
                err = "echo is not supported with stream=true"
            elif body.get("guided_choice") is not None:
                err = "echo cannot be combined with guided_choice"
            elif (sampling.logprobs is not None
                  and any(len(p) > MAX_ECHO_SCORE_TOKENS
                          for p in prompt_ids_list)):
                err = (f"echo with logprobs is limited to "
                       f"{MAX_ECHO_SCORE_TOKENS}-token prompts")
            if err is not None:
                return web.json_response(
                    {"error": {"message": err,
                               "type": "invalid_request_error"}},
                    status=400,
                )
            if body.get("max_tokens") == 0:
                # score-only mode: no generation, just the echoed prompt
                # (with its teacher-forced logprobs when asked)
                return await self._echo_score_response(
                    prompt_ids_list, sampling, rid, created, model, t_start,
                )

        guided = body.get("guided_choice")
        if guided is not None:
            return await self._guided_choice_response(
                request, guided, prompt_ids_list, sampling, rid, created,
                model, chat, stream,
            )

        n = max(1, int(sampling.n))
        nchoices = len(prompt_ids_list) * n

        produce_kv = False
        kv_params = body.get("kv_transfer_params") or {}
        if nchoices == 1:  # disagg handoff is defined per single request
            if kv_params.get("transfer_id") and not kv_params.get(
                    "do_remote_decode"):
                # decode hop of a PUSHED transfer: splice it in
                # decode-ready (no re-prefill). None → not attachable
                # (unknown/incomplete/swept id, no slot, guided params):
                # fall through to the pull import / plain admission of the
                # continuation body — bit-identical greedy either way.
                resp = await self._try_attach_spliced(
                    request, body, kv_params["transfer_id"], sampling,
                    rid, created, model, chat, stream, t_start, deadline,
                )
                if resp is not None:
                    return resp
            if kv_params.get("remote_block_ids"):
                await self._maybe_import_kv(body, prompt_ids_list[0])
            produce_kv = bool(kv_params.get("do_remote_decode"))
        elif kv_params:
            return web.json_response(
                {"error": {"message":
                           "kv_transfer_params requires n=1 and a single prompt",
                           "type": "invalid_request_error"}},
                status=400,
            )

        adapter_slot = self.lora.slot_of(model)
        # resolved once in _run and stashed on the request; fall back to a
        # fresh resolution for callers that enter here directly
        tenant = request.get("tenant") or resolve_tenant(request.headers,
                                                        body)
        ctl = self.brownout
        if ctl is not None and ctl.stage > 0:
            # stage 3: refuse NEW work from over-weight tenants. A pushed
            # P->D continuation is not new work — shedding it would kill a
            # stream whose prefill already ran, so it always passes.
            if (ctl.shed_overweight and tenant in self._brownout_shed
                    and not kv_params.get("transfer_id")):
                ctl.record_shed(SHED_TENANT)
                return self._overloaded(
                    f"brownout stage {ctl.stage}: tenant {tenant!r} is over "
                    "its fair share; new admissions are shed until pressure "
                    "recedes")
            # stage 2: bound tail work by clamping per-request max_tokens
            clamp = ctl.max_tokens_clamp
            if clamp and sampling.max_tokens > clamp:
                ctl.record_shed(SHED_MAX_TOKENS)
                sampling = dataclasses.replace(sampling, max_tokens=clamp)
        reqs, rids = [], []
        for pi, prompt_ids in enumerate(prompt_ids_list):
            for j in range(n):
                idx = pi * n + j
                crid = rid if nchoices == 1 else f"{rid}-{idx}"
                rids.append(crid)
                choice_sampling = sampling
                if sampling.seed is not None and nchoices > 1:
                    # seeded n>1 must still yield distinct choices
                    # (OpenAI/vLLM): derive a per-choice seed
                    choice_sampling = dataclasses.replace(
                        sampling, seed=(sampling.seed + idx) & 0xFFFFFFFF
                    )
                reqs.append((crid, prompt_ids, choice_sampling,
                             adapter_slot, tenant))
        # atomic admission on the engine thread: all requests add or none
        # do, BEFORE this handler commits to a response. Grammar-bank
        # exhaustion and vocab-infeasible grammars (which only surface
        # when the token FSM is built against the real vocabulary) become
        # clean statuses here instead of mid-flight stream errors.
        from production_stack_tpu.engine.engine import GrammarBankFull
        from production_stack_tpu.engine.scheduler import SchedulerQueueFull

        try:
            gens = await self.async_engine.admit_batch(reqs)
        except GrammarBankFull:
            return self._overloaded(
                "all guided-decoding grammar slots are in use; "
                "retry when in-flight guided requests finish")
        except SchedulerQueueFull as e:
            return self._overloaded(str(e))
        except ValueError as e:
            return web.json_response(
                {"error": {"message": str(e),
                           "type": "invalid_request_error"}},
                status=400,
            )
        echo_info = None
        if echo:
            lps_list = []
            for pids in prompt_ids_list:
                lps_list.append(
                    await self.async_engine.run_on_engine(
                        lambda eng, p=pids: eng.prompt_logprobs(p)
                    )
                    if sampling.logprobs is not None else None
                )
            echo_info = {"ids": prompt_ids_list, "lps": lps_list}
        n_prompt = sum(len(p) for p in prompt_ids_list)
        if stream:
            so = body.get("stream_options")
            so = so if isinstance(so, dict) else {}
            return await self._stream_response(
                request, gens, rids, rid, created, model, chat, t_start,
                n_prompt, sampling,
                include_usage=bool(so.get("include_usage")),
                continuous_usage=bool(so.get("continuous_usage_stats")),
                deadline=deadline,
            )
        kv_push = None
        if (produce_kv and kv_params.get("push_url")
                and kv_params.get("transfer_id")):
            kv_push = {"push_url": kv_params["push_url"],
                       "transfer_id": kv_params["transfer_id"],
                       "prompt_ids": prompt_ids_list[0]}
        return await self._full_response(
            gens, rids, rid, created, model, chat, t_start, n_prompt, sampling,
            produce_kv=produce_kv, kv_push=kv_push, echo_info=echo_info,
            deadline=deadline,
        )

    def _overloaded(self, msg: str) -> web.Response:
        """429 with Retry-After: an HONEST overload signal the router's
        circuit breaker respects (fails over now, throttles this backend
        for the advertised interval). The interval is derived from the
        admission queue's depth over its recent drain rate — a deep queue
        behind a slow engine advertises a proportionally longer backoff —
        with ``overload_retry_after`` as the floor."""
        try:
            retry_after = self.engine.scheduler.retry_after_hint(
                floor=self.overload_retry_after)
        except Exception:
            retry_after = self.overload_retry_after
        return web.json_response(
            {"error": {"message": msg, "type": "rate_limit_error"}},
            status=429,
            headers={"Retry-After": f"{retry_after:g}"},
        )

    async def _abort_all(self, tasks, rids):
        """Cancel sibling per-choice tasks (gather doesn't on failure), reap
        them, and abort the engine requests. Returns the reaped results."""
        for t in tasks:
            t.cancel()
        reaped = await asyncio.gather(*tasks, return_exceptions=True)
        for r in rids:
            self.async_engine.abort(r)
        return reaped

    def _check_stop_str(self, text: str, sampling: SamplingParams):
        # cut at the EARLIEST occurrence across all stop strings (vLLM/
        # OpenAI), not the first stop in list order
        cut = None
        for s in sampling.stop:
            idx = text.find(s)
            if idx >= 0 and (cut is None or idx < cut):
                cut = idx
        return None if cut is None else text[:cut]

    async def _try_attach_spliced(self, request, body, tid, sampling, rid,
                                  created, model, chat, stream, t_start,
                                  deadline):
        """Attach a pushed transfer as a decode-ready sequence and serve
        its stream. The continuation body's max_tokens excludes the first
        token (the router already relayed it from the prefill stream), but
        the spliced sequence PRELOADS that token in output_token_ids —
        the engine's length stop counts it, so the splice runs with
        max_tokens + 1 to generate the same remaining span the re-prefill
        fallback would. Returns None when not attachable."""
        self._sweep_kv_transfers()
        state = self._kv_transfers.get(tid)
        if state is None or not state.get("ready"):
            return None
        if sampling.guided_regex or sampling.guided_json:
            # grammar state is built during normal admission; let the
            # re-prefill fallback carry guided continuations
            return None
        from production_stack_tpu.engine.scheduler import SchedulerQueueFull

        meta = state["meta"]
        splice_sampling = dataclasses.replace(
            sampling, max_tokens=sampling.max_tokens + 1)
        try:
            gen = await self.async_engine.attach_spliced(
                rid, meta["prompt_token_ids"], meta["first_token"],
                splice_sampling, state["blocks"],
                tenant=request.get("tenant")
                or resolve_tenant(request.headers, body),
            )
        except (SchedulerQueueFull, ValueError) as e:
            _log.warning("kv transfer %s attach failed (%s); falling back "
                         "to re-prefill", tid, e)
            return None
        # the scheduler owns the blocks now; drop the registry entry so
        # the TTL sweep can never free pages under a live sequence
        self._kv_transfers.pop(tid, None)
        n_prompt = len(meta["prompt_token_ids"]) + 1
        if stream:
            so = body.get("stream_options")
            so = so if isinstance(so, dict) else {}
            return await self._stream_response(
                request, [gen], [rid], rid, created, model, chat, t_start,
                n_prompt, sampling,
                include_usage=bool(so.get("include_usage")),
                continuous_usage=bool(so.get("continuous_usage_stats")),
                deadline=deadline,
            )
        return await self._full_response(
            [gen], [rid], rid, created, model, chat, t_start, n_prompt,
            sampling, deadline=deadline,
        )

    async def _full_response(self, gens, rids, rid, created, model, chat,
                             t_start, n_prompt, sampling,
                             produce_kv=False, kv_push=None,
                             echo_info=None, deadline=None) -> web.Response:
        tk = self.engine.tokenizer

        async def collect(gen, crid):
            token_ids: list[int] = []
            lps: list = []
            finish_reason = None
            first_token_t = None
            cached = 0
            final_blocks = None
            async for out in gen:
                if first_token_t is None:
                    first_token_t = time.monotonic()
                if out.finished:
                    self._observe_finished(rid, out)
                token_ids.extend(out.new_token_ids)
                if out.new_logprobs:
                    lps.extend(out.new_logprobs)
                cached = out.num_cached_tokens
                if out.block_ids is not None:
                    final_blocks = out.block_ids
                finish_reason = out.finish_reason or finish_reason
                text = tk.decode(token_ids)
                stopped = self._check_stop_str(text, sampling)
                if stopped is not None:
                    self.async_engine.abort(crid)
                    # count only the tokens that contribute to the kept text
                    n_kept = _tokens_covering(tk, token_ids, len(stopped))
                    return (stopped, n_kept, "stop", first_token_t, cached,
                            final_blocks, token_ids[:n_kept], lps[:n_kept])
            return (tk.decode(token_ids), len(token_ids), finish_reason,
                    first_token_t, cached, final_blocks, token_ids, lps)

        tasks = [asyncio.ensure_future(collect(g, r))
                 for g, r in zip(gens, rids)]
        try:
            if deadline is not None:
                results = await asyncio.wait_for(
                    asyncio.gather(*tasks), deadline - time.time())
            else:
                results = await asyncio.gather(*tasks)
        except asyncio.TimeoutError:
            # deadline expired mid-generation: remove the sequences from
            # the scheduler and free their KV blocks before answering
            await self._abort_all(tasks, rids)
            return web.json_response(
                {"error": {"message": "request deadline exceeded",
                           "type": "timeout_error"}},
                status=504,
            )
        except asyncio.CancelledError:
            # client disconnected while we buffered the whole response:
            # without this the sequences would decode to completion with
            # nobody reading (KV blocks + slots held the entire time)
            await self._abort_all(tasks, rids)
            raise
        except ValueError as e:
            await self._abort_all(tasks, rids)
            return web.json_response(
                {"error": {"message": str(e), "type": "invalid_request_error"}},
                status=400,
            )
        end = time.monotonic()
        first_times = [r[3] for r in results if r[3] is not None]
        first_token_t = min(first_times) if first_times else None
        n_completion = sum(r[1] for r in results)
        self.metrics.observe_request(t_start, first_token_t, end, n_completion)
        # cached tokens: all n choices of one prompt hit the same cached
        # prefix (max per prompt), distinct prompts cache independently (sum
        # across prompts)
        n = max(1, int(sampling.n))
        cached = sum(
            max((r[4] for r in results[pi * n : (pi + 1) * n]), default=0)
            for pi in range(len(results) // n)
        )
        usage = {
            "prompt_tokens": n_prompt,
            "completion_tokens": n_completion,
            "total_tokens": n_prompt + n_completion,
            "prompt_tokens_details": {"cached_tokens": cached},
        }
        choices = []
        want_lp = sampling.logprobs is not None
        for idx, (text, _n, finish_reason, _t, _c, _b, ids, lps) in enumerate(
            results
        ):
            if chat:
                choice = {
                    "index": idx,
                    "message": {"role": "assistant", "content": text},
                    "finish_reason": finish_reason or "stop",
                }
                if want_lp:
                    choice["logprobs"] = _fmt_chat_logprobs(
                        tk, ids, lps, sampling.logprobs
                    )
                choices.append(choice)
            else:
                if echo_info is not None:
                    # echo: prepend the prompt (and, with logprobs, its
                    # teacher-forced entries — token 0 has no prediction)
                    pi = idx // n
                    p_ids = echo_info["ids"][pi]
                    text = tk.decode(p_ids) + text
                    if want_lp:
                        ids = list(p_ids) + list(ids)
                        lps = ([(None, [])] + echo_info["lps"][pi]
                               + list(lps))
                choices.append({
                    "index": idx,
                    "text": text,
                    "finish_reason": finish_reason or "stop",
                    "logprobs": (
                        _fmt_completion_logprobs(tk, ids, lps,
                                                 sampling.logprobs)
                        if want_lp else None
                    ),
                })
        obj = "chat.completion" if chat else "text_completion"
        payload = {
            "id": rid,
            "object": obj,
            "created": created,
            "model": model,
            "choices": choices,
            "usage": usage,
        }
        final_blocks = results[0][5] if results else None
        if produce_kv and final_blocks:
            # producer side of the P→D handoff: hand the router/decoder the
            # block handles (reference: engine-native kv_transfer_params,
            # request.py:827-837; router fills remote_host)
            payload["kv_transfer_params"] = {
                "do_remote_prefill": True,
                "remote_engine_id": self.model_name,
                "remote_block_ids": final_blocks,
                "remote_host": None,
                "remote_port": None,
            }
            if kv_push is not None and results[0][6]:
                # streamed push to the chosen decode engine; the pull
                # fields above stay as the fallback if the push dies
                pushed = await self._push_kv_blocks(
                    kv_push["push_url"], kv_push["transfer_id"],
                    final_blocks, kv_push["prompt_ids"], results[0][6][0],
                )
                payload["kv_transfer_params"]["transfer_id"] = \
                    kv_push["transfer_id"]
                payload["kv_transfer_params"]["pushed"] = pushed
        return web.json_response(payload)

    async def _echo_score_response(self, prompt_ids_list, sampling, rid,
                                   created, model, t_start) -> web.Response:
        """completions echo + max_tokens=0: return the prompt itself, with
        its teacher-forced logprobs when asked — the OpenAI scoring mode
        (classification/perplexity without generating anything). With n>1
        the (deterministic) scored choice repeats per the prompt*n choice
        layout the generation path uses."""
        tk = self.engine.tokenizer
        n = max(1, int(sampling.n))
        choices = []
        for pi, pids in enumerate(prompt_ids_list):
            lp_obj = None
            if sampling.logprobs is not None:
                entries = await self.async_engine.run_on_engine(
                    lambda eng, p=pids: eng.prompt_logprobs(p)
                )
                lp_obj = _fmt_completion_logprobs(
                    tk, list(pids), [(None, [])] + entries,
                    sampling.logprobs,
                )
            for j in range(n):
                choices.append({
                    "index": pi * n + j,
                    "text": tk.decode(list(pids)),
                    "finish_reason": "length",
                    "logprobs": lp_obj,
                })
        n_prompt = sum(len(p) for p in prompt_ids_list)
        self.metrics.observe_request(t_start, None, time.monotonic(), 0)
        return web.json_response({
            "id": rid, "object": "text_completion", "created": created,
            "model": model, "choices": choices,
            "usage": {"prompt_tokens": n_prompt, "completion_tokens": 0,
                      "total_tokens": n_prompt},
        })

    async def _guided_choice_response(self, request, guided, prompt_ids_list,
                                      sampling, rid, created, model,
                                      chat, stream) -> web.StreamResponse:
        """vLLM's guided_choice, scored at the SEQUENCE level: one batched
        teacher-forced pass computes log P(choice | prompt) for every
        choice; temperature 0 picks the argmax, otherwise the choice is
        sampled from softmax(logP / T). Exactly one of the given strings is
        returned — with principled whole-sequence probabilities rather
        than the reference engines' greedy token-walk approximation."""
        import numpy as np

        if (not isinstance(guided, list) or not guided
                or not all(isinstance(c, str) and c for c in guided)
                or len(guided) > 64):
            return web.json_response(
                {"error": {"message": "guided_choice must be 1..64 "
                           "non-empty strings",
                           "type": "invalid_request_error"}},
                status=400,
            )
        if len(prompt_ids_list) != 1 or sampling.n != 1:
            return web.json_response(
                {"error": {"message": "guided_choice requires a single "
                           "prompt and n=1",
                           "type": "invalid_request_error"}},
                status=400,
            )
        tk = self.engine.tokenizer
        prompt_ids = prompt_ids_list[0]
        # continuations must NOT carry a BOS: the choice is scored
        # mid-sequence, conditioned on the prompt
        choice_ids = [tk.encode(c, add_bos=False) for c in guided]
        if any(not c for c in choice_ids):
            return web.json_response(
                {"error": {"message": "guided_choice entry tokenizes to "
                           "nothing", "type": "invalid_request_error"}},
                status=400,
            )
        if (len(prompt_ids) + max(len(c) for c in choice_ids)
                > self.config.model.max_model_len):
            return web.json_response(
                {"error": {"message": "prompt + longest choice exceeds "
                           "max_model_len",
                           "type": "invalid_request_error"}},
                status=400,
            )
        logps = await self.async_engine.run_on_engine(
            lambda eng: eng.choice_logprobs(prompt_ids, choice_ids)
        )
        if sampling.temperature <= 0.0:
            idx = int(np.argmax(logps))
        else:
            z = np.asarray(logps, np.float64) / sampling.temperature
            p = np.exp(z - z.max())
            p /= p.sum()
            rng = np.random.default_rng(sampling.seed)
            idx = int(rng.choice(len(p), p=p))
        text = guided[idx]
        usage = {  # OpenAI semantics: the client's one prompt, counted once
            "prompt_tokens": len(prompt_ids),
            "completion_tokens": len(choice_ids[idx]),
            "total_tokens": len(prompt_ids) + len(choice_ids[idx]),
        }
        if chat:
            choice = {"index": 0,
                      "message": {"role": "assistant", "content": text},
                      "finish_reason": "stop", "logprobs": None}
            obj = "chat.completion"
        else:
            choice = {"index": 0, "text": text, "finish_reason": "stop",
                      "logprobs": None}
            obj = "text_completion"
        if not stream:
            return web.json_response({
                "id": rid, "object": obj, "created": created,
                "model": model, "choices": [choice], "usage": usage,
            })
        resp = web.StreamResponse(
            status=200,
            headers={"Content-Type": "text/event-stream",
                     "Cache-Control": "no-cache", "X-Request-Id": rid},
        )
        await resp.prepare(request)
        obj_chunk = "chat.completion.chunk" if chat else "text_completion"
        if chat:
            chunks = [
                {"delta": {"role": "assistant", "content": text},
                 "index": 0, "finish_reason": None},
                {"delta": {}, "index": 0, "finish_reason": "stop"},
            ]
        else:
            chunks = [
                {"text": text, "index": 0, "finish_reason": None},
                {"text": "", "index": 0, "finish_reason": "stop"},
            ]
        for c in chunks:
            payload = {"id": rid, "object": obj_chunk, "created": created,
                       "model": model, "choices": [c]}
            await resp.write(f"data: {json.dumps(payload)}\n\n".encode())
        await resp.write(b"data: [DONE]\n\n")
        await resp.write_eof()
        return resp

    async def _stream_response(self, request, gens, rids, rid, created, model,
                               chat, t_start, n_prompt, sampling,
                               include_usage=False, continuous_usage=False,
                               deadline=None) -> web.StreamResponse:
        resp = web.StreamResponse(
            status=200,
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                # echo the propagated id so direct clients (no router in
                # front) can join logs/flight records too
                "X-Request-Id": request.headers.get("x-request-id") or rid,
            },
        )
        await resp.prepare(request)
        tk = self.engine.tokenizer
        obj = "chat.completion.chunk" if chat else "text_completion"
        write_lock = asyncio.Lock()

        async def send(payload: dict) -> None:
            async with write_lock:
                await resp.write(f"data: {json.dumps(payload)}\n\n".encode())

        if chat:
            for idx in range(len(gens)):
                await send(
                    {
                        "id": rid, "object": obj, "created": created,
                        "model": model,
                        "choices": [
                            {"index": idx, "delta": {"role": "assistant"},
                             "finish_reason": None}
                        ],
                    }
                )

        # A stop sequence can span chunk boundaries; hold back enough trailing
        # chars that a stop prefix is never streamed before it is confirmed
        # not to be one.
        holdback = max((len(s) for s in sampling.stop), default=1) - 1
        shared = {"first_token_t": None, "first_chunk_written": False}
        # per-choice generated-token counts for continuous_usage_stats
        # (vLLM stream_options extension): every content chunk carries
        # cumulative usage so a mid-stream death leaves the router's
        # resume accounting token-exact, not event-count-approximate
        kept_so_far: dict = {}

        want_lp = sampling.logprobs is not None

        async def stream_one(gen, crid, idx) -> int:
            token_ids: list[int] = []
            # the text so far without decoding the whole list a token
            # (engine/tokenizer.py WindowedDecoder); the last output's
            # text is the whole list's, whatever a window held back
            text_of = tk.stream_decoder()
            all_lps: list = []
            lp_emitted = 0
            sent_len = 0
            finish_reason = None
            n_kept = 0
            async for out in gen:
                if shared["first_token_t"] is None:
                    shared["first_token_t"] = time.monotonic()
                if out.finished:
                    self._observe_finished(rid, out)
                token_ids.extend(out.new_token_ids)
                if out.new_logprobs:
                    all_lps.extend(out.new_logprobs)
                text = (tk.decode(token_ids) if out.finished
                        else text_of(token_ids))
                stopped = self._check_stop_str(text, sampling)
                if stopped is not None:
                    self.async_engine.abort(crid)
                    text = stopped
                    finish_reason = "stop"
                    n_kept = _tokens_covering(tk, token_ids, len(stopped))
                else:
                    n_kept = len(token_ids)
                done = out.finished or finish_reason is not None
                limit = (len(text) if done or not holdback
                         else max(sent_len, len(text) - holdback))
                delta = text[sent_len:limit]
                sent_len = limit
                if delta or done:
                    fr = finish_reason or out.finish_reason
                    # chunk logprobs cover tokens whose text is FULLY sent:
                    # the stop-string holdback must gate entries too, or a
                    # token later cut by the stop leaks its string/logprob
                    chunk_lp = None
                    if want_lp:
                        m = _tokens_covering(tk, token_ids, sent_len)
                        if (m and
                                len(tk.decode(token_ids[:m])) > sent_len):
                            m -= 1  # last token's text not fully sent yet
                        hi = min(n_kept, len(all_lps), m)
                        if lp_emitted < hi:
                            span = token_ids[lp_emitted:hi]
                            span_lps = all_lps[lp_emitted:hi]
                            if chat:
                                chunk_lp = _fmt_chat_logprobs(
                                    tk, span, span_lps, sampling.logprobs
                                )
                            else:
                                off = len(tk.decode(token_ids[:lp_emitted]))
                                chunk_lp = _fmt_completion_logprobs(
                                    tk, span, span_lps, sampling.logprobs,
                                    offset0=off,
                                )
                            lp_emitted = hi
                    if chat:
                        choice = {"index": idx,
                                  "delta": {"content": delta} if delta else {},
                                  "finish_reason": fr if done else None}
                        if want_lp:
                            choice["logprobs"] = chunk_lp
                    else:
                        choice = {"index": idx, "text": delta,
                                  "logprobs": chunk_lp,
                                  "finish_reason": fr if done else None}
                    chunk = {"id": rid, "object": obj, "created": created,
                             "model": model, "choices": [choice]}
                    if continuous_usage:
                        kept_so_far[idx] = n_kept
                        n_gen = sum(kept_so_far.values())
                        chunk["usage"] = {
                            "prompt_tokens": n_prompt,
                            "completion_tokens": n_gen,
                            "total_tokens": n_prompt + n_gen,
                        }
                    await send(chunk)
                    if not shared["first_chunk_written"]:
                        # once a request: when the event loop had written
                        # the first generated chunk to the socket
                        shared["first_chunk_written"] = True
                        inflight = self._inflight.get(rid)
                        if inflight is not None:
                            self.flight_recorder.stamp(
                                inflight, "first_chunk_written")
                            inflight["steps"]["first_chunk_written"] = (
                                self.engine.clock.step_num)
                if finish_reason is not None:
                    break
            return n_kept

        n_out = 0
        tasks = [asyncio.ensure_future(stream_one(g, r, i))
                 for i, (g, r) in enumerate(zip(gens, rids))]
        try:
            if deadline is not None:
                kept = await asyncio.wait_for(
                    asyncio.gather(*tasks), deadline - time.time())
            else:
                kept = await asyncio.gather(*tasks)
            n_out = sum(kept)
        except asyncio.TimeoutError:
            # deadline expired mid-stream: abort (frees KV), then tell the
            # client in-band before [DONE] — the stream already committed 200
            reaped = await self._abort_all(tasks, rids)
            n_out = sum(r for r in reaped if isinstance(r, int))
            inflight = self._inflight.get(rid)
            if inflight is not None:  # a 200 stream that timed out in-band
                inflight["outcome"] = "deadline_exceeded"
            await send({"error": {"message": "request deadline exceeded",
                                  "type": "timeout_error"}})
        except ValueError as e:
            reaped = await self._abort_all(tasks, rids)
            # count whatever completed choices managed to stream so the
            # usage chunk / metrics don't report 0 for partial failures
            n_out = sum(r for r in reaped if isinstance(r, int))
            await send({"error": {"message": str(e)}})
        except (ConnectionResetError, asyncio.CancelledError):
            # cancel siblings before teardown so no task writes to the
            # closed response
            await self._abort_all(tasks, rids)
            raise
        end = time.monotonic()
        self.metrics.observe_request(t_start, shared["first_token_t"], end,
                                     n_out)
        if include_usage:
            # final usage chunk (OpenAI stream_options.include_usage shape)
            await send({
                "id": rid, "object": obj, "created": created, "model": model,
                "choices": [],
                "usage": {
                    "prompt_tokens": n_prompt,
                    "completion_tokens": n_out,
                    "total_tokens": n_prompt + n_out,
                },
            })
        await resp.write(b"data: [DONE]\n\n")
        await resp.write_eof()
        return resp


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser("production-stack-tpu engine server")
    p.add_argument("--model", default="tiny-llama",
                   help="preset name or local HF model directory")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--max-model-len", type=int, default=None)
    p.add_argument("--max-num-seqs", type=int, default=None)
    p.add_argument("--block-size", type=int, default=None)
    p.add_argument("--num-blocks", type=int, default=None)
    p.add_argument("--tensor-parallel-size", type=int, default=-1)
    p.add_argument("--data-parallel-size", type=int, default=1)
    p.add_argument("--dtype", default=None)
    p.add_argument("--quantization", default=None, choices=["int8"],
                   help="serve W8A8 int8 (per-channel weight + dynamic "
                        "per-token activation scales on the MXU int8 path; "
                        "halves weight HBM traffic — engine/quant.py)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--served-model-name", default=None)
    p.add_argument("--num-scheduler-steps", type=int, default=None,
                   help="decode iterations fused per dispatch (multi-step)")
    p.add_argument("--max-num-batched-tokens", type=int, default=None)
    p.add_argument("--attention-impl", default=None, type=_attention_impl,
                   help="accepted and ignored ('auto' or 'ragged'): every "
                        "prompt runs through the ragged step, and "
                        "--max-num-batched-tokens is its only shape knob")
    p.add_argument("--speculative-ngram", type=int, default=0,
                   help="n-gram (prompt-lookup) speculative decoding: "
                        "propose up to this many draft tokens per step from "
                        "the sequence's own history and verify them inside "
                        "the ragged unified dispatch (vLLM "
                        "--speculative-config ngram equivalent). Per-"
                        "sequence: greedy rows speculate, sampled/penalised "
                        "rows in the same batch decode normally; an "
                        "acceptance EWMA adapts the width per sequence. "
                        "0 = off")
    p.add_argument("--speculative-ngram-max", type=int, default=3,
                   help="longest tail n-gram matched against the history")
    p.add_argument("--speculative-ngram-min", type=int, default=1,
                   help="shortest tail n-gram matched against the history "
                        "(the proposer tries max..min, longest first)")
    p.add_argument("--speculative-window", type=int, default=4096,
                   help="trailing history tokens the n-gram proposer "
                        "searches for a recurrence")
    p.add_argument("--fault-injection", default=None,
                   help="inject faults on the OpenAI surface for "
                        "resilience drills, e.g. error_rate=0.3,"
                        "latency_ms=100,stall_ms=500,stream_abort_rate=0.1 "
                        "(testing/faults.py)")
    p.add_argument("--max-queue-len", type=int, default=None,
                   help="waiting-queue bound; admissions past it get 429 "
                        "+ Retry-After so the router fails over instead "
                        "of piling onto an overloaded engine (0 = "
                        "unbounded)")
    p.add_argument("--overload-retry-after", type=float, default=1.0,
                   help="floor for the Retry-After seconds advertised on "
                        "overload 429s (the actual value scales with queue "
                        "depth over the recent admission drain rate)")
    p.add_argument("--fair-share", action="store_true",
                   help="per-tenant deficit-round-robin scheduling: split "
                        "the prefill token budget across tenants with "
                        "pending work by weight and a weighted-fair "
                        "admission dequeue, so one flooding tenant queues "
                        "behind everyone else instead of starving them. "
                        "With a single active tenant the schedule is "
                        "bit-identical to FCFS")
    p.add_argument("--tenant-weights", default=None,
                   help="JSON object tenant -> relative weight for "
                        "--fair-share and stage-3 brownout shedding, e.g. "
                        "'{\"team-a\": 3, \"team-b\": 1}'; unlisted "
                        "tenants weigh 1.0")
    p.add_argument("--brownout", action="store_true",
                   help="staged brownout degradation under sustained "
                        "pressure (queue depth, HBM occupancy, watchdog "
                        "stall): stage 1 sheds speculative-decode grants, "
                        "stage 2 clamps max_tokens and pauses KV "
                        "prefetch, stage 3 sheds over-weight tenants' new "
                        "admissions; recovery needs sustained calm")
    p.add_argument("--brownout-interval", type=float, default=2.0,
                   help="seconds between brownout pressure evaluations")
    p.add_argument("--brownout-queue-high", type=float, default=0.5,
                   help="waiting/max-queue-len fraction treated as hot")
    p.add_argument("--brownout-hbm-high", type=float, default=0.92,
                   help="HBM used/total fraction treated as hot")
    p.add_argument("--brownout-up-evals", type=int, default=2,
                   help="consecutive hot evaluations per stage up")
    p.add_argument("--brownout-calm-evals", type=int, default=3,
                   help="consecutive calm evaluations per stage down")
    p.add_argument("--brownout-max-tokens-clamp", type=int, default=256,
                   help="stage-2 per-request max_tokens ceiling")
    p.add_argument("--drain-deadline", type=float, default=30.0,
                   help="graceful-drain budget (seconds): on SIGTERM or "
                        "POST /drain, in-flight sequences get this long "
                        "to finish before stragglers are aborted (KV "
                        "blocks freed) and the process exits; readiness "
                        "(GET /ready) answers 503 for the whole window "
                        "while /health stays truthful")
    p.add_argument("--watchdog-stall-seconds", type=float, default=0.0,
                   help="stuck-step watchdog: flip readiness (GET /ready) "
                        "to 503 when no scheduler step completes for this "
                        "many seconds while work is queued — a wedged XLA "
                        "dispatch blocks the engine thread but not this "
                        "detector thread, so the router ejects the pod "
                        "within one probe interval. 0 = disabled")
    p.add_argument("--no-diagnostics", dest="diagnostics",
                   action="store_false", default=True,
                   help="disable anomaly-triggered diagnostic bundles "
                        "(engine/diagnostics.py: unexpected recompile, "
                        "watchdog stall, drain-deadline abort and HBM "
                        "pressure each capture evidence to "
                        "GET /debug/diagnostics)")
    p.add_argument("--diagnostics-dir", default="",
                   help="bundle archive directory (default: a per-pid "
                        "directory under the system tmpdir)")
    p.add_argument("--diagnostics-max-bundles", type=int, default=16,
                   help="bundle count retention cap — oldest evicted first")
    p.add_argument("--diagnostics-max-bytes", type=int,
                   default=256 * 1024 * 1024,
                   help="bundle archive size cap in bytes")
    p.add_argument("--diagnostics-cooldown", type=float, default=60.0,
                   help="minimum seconds between captures of the SAME "
                        "trigger (a recompile storm produces one bundle, "
                        "not a bundle per recompile)")
    p.add_argument("--diagnostics-profile-seconds", type=float, default=2.0,
                   help="jax profiler trace length captured into each "
                        "bundle (capped at 10; 0 disables the trace — "
                        "the JSON snapshots are still captured)")
    p.add_argument("--diagnostics-hbm-threshold", type=float, default=0.92,
                   help="HBM occupancy fraction that fires the "
                        "hbm_pressure capture trigger")
    p.add_argument("--otel-endpoint", default=None,
                   help="OTLP gRPC endpoint; engine spans JOIN the "
                        "router's trace via the propagated traceparent "
                        "(requires opentelemetry-sdk in the image; "
                        "degrades to propagation-only without it)")
    p.add_argument("--otel-service-name", default="tpu-engine")
    p.add_argument("--otel-secure", action="store_true",
                   help="use TLS for the OTLP exporter connection")
    p.add_argument("--flight-recorder-size", type=int, default=256,
                   help="per-request timelines kept in the /debug/requests "
                        "ring buffer")
    p.add_argument("--skip-warmup", action="store_true",
                   help="skip startup compilation of all shape variants")
    p.add_argument("--no-perf-accounting", dest="perf_accounting",
                   action="store_false", default=True,
                   help="disable live goodput accounting (MFU / HBM "
                        "bandwidth gauges, compile-event tracking, "
                        "GET /debug/perf — engine/perf_accounting.py)")
    p.add_argument("--perf-window", type=float, default=60.0,
                   help="sliding window (seconds) the utilization gauges "
                        "are computed over")
    p.add_argument("--no-tenant-metering", dest="tenant_metering",
                   action="store_false", default=True,
                   help="disable per-tenant token/chip-second attribution "
                        "(vllm:tenant_* series, GET /debug/tenants, usage "
                        "ledger — production_stack_tpu/tenancy.py). "
                        "Observe-only either way: total metrics are "
                        "bit-identical with metering on or off")
    p.add_argument("--tenant-top-k", type=int, default=8,
                   help="tenants exported individually per metric; the "
                        "remainder folds into tenant=\"other\" (bounded "
                        "label cardinality)")
    p.add_argument("--tenant-ledger-path", default="",
                   help="rotating JSONL usage-ledger path (one record per "
                        "finished request: tenant, model, tokens by phase, "
                        "chip-seconds, stage stamps); empty = ledger off")
    p.add_argument("--tenant-ledger-max-bytes", type=int, default=16 << 20,
                   help="ledger rotation threshold in bytes")
    p.add_argument("--perf-peak-tflops", type=float, default=0.0,
                   help="accelerator peak bf16 TFLOP/s for MFU; 0 = the "
                        "published peak of the device_kind this runs on "
                        "(perf_accounting.DEVICE_PEAKS) — no entry, no "
                        "utilization gauge")
    p.add_argument("--perf-peak-hbm-gbps", type=float, default=0.0,
                   help="accelerator peak HBM GB/s; 0 = by device_kind")
    p.add_argument("--perf-peak-ici-gbps", type=float, default=0.0,
                   help="per-chip ICI GB/s for the collective roofline "
                        "(multi-chip meshes); 0 = by device_kind")
    p.add_argument("--host-offload-blocks", type=int, default=0,
                   help="host-DRAM KV tier capacity in blocks (0 = off; "
                        "prefer --kv-host-cache-bytes)")
    p.add_argument("--kv-host-cache-bytes", type=int, default=0,
                   help="host-DRAM KV tier capacity in BYTES (the "
                        "authoritative knob; overrides "
                        "--host-offload-blocks when both are set)")
    p.add_argument("--kv-prefetch-workers", type=int, default=0,
                   help="background threads for the async warm-tier "
                        "prefix prefetch pipeline (0 = config default)")
    p.add_argument("--remote-kv-url", default=None,
                   help="shared remote KV server URL (kv_server)")
    # -- disaggregated prefill/decode (engine/kv_transfer.py) ------------
    p.add_argument("--role", default="unified",
                   choices=["unified", "prefill", "decode"],
                   help="engine role in a disaggregated deployment: "
                        "'prefill' runs prompts to first token and "
                        "streams the KV to a decode engine (POST "
                        "{decode}/kv/recv), 'decode' accepts pushed "
                        "transfers and splices them in decode-ready, "
                        "'unified' (default) does both in one pool. "
                        "Advisory for routing: every role still serves "
                        "the full OpenAI surface, so a degraded fleet "
                        "can fall back to unified serving")
    p.add_argument("--kv-transfer-group-layers", type=int, default=0,
                   help="layers per KV-transfer frame (pipelined "
                        "gather/send/scatter granularity); 0 = half the "
                        "layer stack (kv_transfer.default_group)")
    p.add_argument("--kv-transfer-window", type=int, default=2,
                   help="producer-side in-flight device gathers ahead of "
                        "the frame being sent (bounded pipeline depth)")
    p.add_argument("--kv-transfer-retries", type=int, default=3,
                   help="push attempts per transfer; digest-mismatch "
                        "retries resume from the first unacknowledged "
                        "layer group instead of resending the transfer")
    p.add_argument("--kv-transfer-ttl", type=float, default=120.0,
                   help="seconds a received-but-unattached transfer may "
                        "hold KV blocks on the decode engine before the "
                        "sweep frees them (covers a router that died "
                        "between the push and the decode hop)")
    # -- multi-host serving (replaces the reference's KubeRay + Ray
    # executor: helm/templates/ray-cluster.yaml:332-335,716-717 there).
    # Defaults come from env (PSTPU_COORDINATOR / PSTPU_NUM_PROCESSES /
    # PSTPU_PROCESS_ID / PSTPU_CONTROL_PORT) so the chart's StatefulSet
    # wires them without templating argv (parallel/distributed.py).
    p.add_argument("--distributed-coordinator", default=None,
                   help="host:port of process 0's jax.distributed "
                        "coordinator (multi-host serving; env "
                        "PSTPU_COORDINATOR)")
    p.add_argument("--num-processes", type=int, default=None,
                   help="total controller processes in the multi-host "
                        "group (env PSTPU_NUM_PROCESSES)")
    p.add_argument("--process-id", type=int, default=None,
                   help="this process's id; 0 serves HTTP and leads, "
                        ">0 replays step plans (env PSTPU_PROCESS_ID)")
    p.add_argument("--control-port", type=int, default=None,
                   help="leader's step-plan broadcast port "
                        "(engine/multihost.py; env PSTPU_CONTROL_PORT)")
    p.add_argument("--config", default=None,
                   help="YAML file of flag values (keys = flag names); "
                        "explicit CLI flags win (yaml_args.py)")
    return p


def _attention_impl(value: str) -> str:
    """``--attention-impl``: names the one dispatch family there is."""
    if value in ("auto", "ragged"):
        return value
    raise argparse.ArgumentTypeError(
        f"{value!r}: choose 'auto' or 'ragged', or drop the flag (the "
        "bucketed prefill family was removed: every prompt runs through "
        "the ragged step)")


def config_from_args(args) -> EngineConfig:
    import dataclasses

    from production_stack_tpu.parallel.mesh import MeshConfig

    overrides = {}
    if args.max_model_len:
        overrides["max_model_len"] = args.max_model_len
    if args.dtype:
        overrides["dtype"] = args.dtype
    if args.quantization:
        overrides["quant"] = args.quantization
    cfg = EngineConfig.for_model(args.model, **overrides)
    if args.served_model_name:
        cfg.model = dataclasses.replace(cfg.model, name=args.served_model_name)
    if args.max_num_seqs:
        cfg.scheduler.max_num_seqs = args.max_num_seqs
    if args.block_size:
        cfg.cache.block_size = args.block_size
    if args.num_blocks:
        cfg.cache.num_blocks = args.num_blocks
    if args.num_scheduler_steps:
        cfg.scheduler.multi_step = args.num_scheduler_steps
    if args.max_num_batched_tokens:
        cfg.scheduler.max_num_batched_tokens = args.max_num_batched_tokens
    if args.speculative_ngram:
        cfg.scheduler.spec_ngram_k = args.speculative_ngram
        cfg.scheduler.spec_ngram_max = args.speculative_ngram_max
        cfg.scheduler.spec_ngram_min = args.speculative_ngram_min
        cfg.scheduler.spec_window = args.speculative_window
    if args.max_queue_len is not None:
        cfg.scheduler.max_queue_len = args.max_queue_len
    if getattr(args, "fair_share", False):
        cfg.scheduler.fair_share = True
    if getattr(args, "tenant_weights", None):
        try:
            weights = json.loads(args.tenant_weights)
        except ValueError as e:
            raise SystemExit(f"--tenant-weights is not valid JSON: {e}")
        if not isinstance(weights, dict):
            raise SystemExit("--tenant-weights must be a JSON object "
                             "(tenant -> weight)")
        cfg.scheduler.tenant_weights = weights
    if args.host_offload_blocks:
        cfg.cache.host_offload_blocks = args.host_offload_blocks
    if getattr(args, "kv_host_cache_bytes", 0):
        cfg.cache.kv_host_cache_bytes = args.kv_host_cache_bytes
    if getattr(args, "kv_prefetch_workers", 0):
        cfg.cache.kv_prefetch_workers = args.kv_prefetch_workers
    if args.remote_kv_url:
        cfg.cache.remote_kv_url = args.remote_kv_url
    cfg.role = getattr(args, "role", "unified") or "unified"
    cfg.kv_transfer_group_layers = getattr(
        args, "kv_transfer_group_layers", 0) or 0
    cfg.kv_transfer_window = getattr(args, "kv_transfer_window", 2) or 2
    cfg.kv_transfer_retries = getattr(args, "kv_transfer_retries", 3) or 3
    cfg.kv_transfer_ttl = getattr(args, "kv_transfer_ttl", 120.0) or 120.0
    cfg.mesh = MeshConfig(
        data=args.data_parallel_size, tensor=args.tensor_parallel_size,
    )
    cfg.perf.enabled = getattr(args, "perf_accounting", True)
    if getattr(args, "perf_window", None):
        cfg.perf.window = args.perf_window
    if getattr(args, "perf_peak_tflops", 0.0):
        cfg.perf.peak_tflops = args.perf_peak_tflops
    if getattr(args, "perf_peak_hbm_gbps", 0.0):
        cfg.perf.peak_hbm_gbps = args.perf_peak_hbm_gbps
    if getattr(args, "perf_peak_ici_gbps", 0.0):
        cfg.perf.peak_ici_gbps = args.perf_peak_ici_gbps
    cfg.tenant_metering = getattr(args, "tenant_metering", True)
    cfg.tenant_top_k = getattr(args, "tenant_top_k", 8) or 8
    cfg.tenant_ledger_path = getattr(args, "tenant_ledger_path", "") or ""
    cfg.tenant_ledger_max_bytes = (
        getattr(args, "tenant_ledger_max_bytes", 16 << 20) or (16 << 20))
    cfg.seed = args.seed
    return cfg


def brownout_from_args(args) -> Optional[BrownoutController]:
    """Build the staged-brownout controller from CLI flags (None when the
    feature is off — the default)."""
    if not getattr(args, "brownout", False):
        return None
    from production_stack_tpu.engine.overload import BrownoutConfig

    return BrownoutController(BrownoutConfig(
        enabled=True,
        interval=getattr(args, "brownout_interval", 2.0),
        queue_high=getattr(args, "brownout_queue_high", 0.5),
        hbm_high=getattr(args, "brownout_hbm_high", 0.92),
        up_evals=getattr(args, "brownout_up_evals", 2),
        calm_evals=getattr(args, "brownout_calm_evals", 3),
        max_tokens_clamp=getattr(args, "brownout_max_tokens_clamp", 256),
    ))


def diagnostics_config_from_args(args) -> DiagnosticsConfig:
    return DiagnosticsConfig(
        enabled=getattr(args, "diagnostics", True),
        dir=getattr(args, "diagnostics_dir", ""),
        max_bundles=getattr(args, "diagnostics_max_bundles", 16),
        max_bytes=getattr(args, "diagnostics_max_bytes", 256 * 1024 * 1024),
        cooldown=getattr(args, "diagnostics_cooldown", 60.0),
        profile_seconds=getattr(args, "diagnostics_profile_seconds", 2.0),
        hbm_threshold=getattr(args, "diagnostics_hbm_threshold", 0.92),
    )


def _follower_main(config: EngineConfig, dist, http_host: str,
                   http_port: int) -> None:
    """Follower process: build the identical runner shard, serve a
    minimal /health for K8s probes, replay the leader's step plans until
    the control channel closes."""
    import json as _json
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from production_stack_tpu.engine.model_runner import ModelRunner
    from production_stack_tpu.engine.multihost import follower_loop
    from production_stack_tpu.parallel.mesh import build_mesh

    class _Health(BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 (http.server API)
            body = _json.dumps({
                "status": "follower", "process_id": dist.process_id,
            }).encode()
            self.send_response(200 if self.path == "/health" else 404)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):  # quiet
            pass

    httpd = ThreadingHTTPServer((http_host, http_port), _Health)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()

    # the same runner-construction sequence as LLMEngine.__init__ — each
    # process must issue the identical device programs in the identical
    # order (param init, quantization, KV-pool allocation)
    mesh = build_mesh(config.mesh)
    runner = ModelRunner(config, mesh, None, None)
    try:
        follower_loop(runner, dist.coordinator_host, dist.control_port)
    finally:
        httpd.shutdown()


def main(argv=None) -> None:
    import atexit
    import os
    import signal

    # the process's creation to here, `process`, is the start's first span
    start = etracing.StartClock()
    from production_stack_tpu.yaml_args import parse_with_yaml_config

    args = parse_with_yaml_config(build_parser(), argv)
    # per-request completion lines (x-request-id correlation,
    # docs/observability.md) are INFO on "engine.server"; give that logger
    # a handler when the embedding process hasn't configured logging
    import logging  # the multihost branch below has a local import too

    if not logging.getLogger().handlers and not _log.handlers:
        handler = logging.StreamHandler()
        handler.setFormatter(logging.Formatter(
            "[%(asctime)s] %(levelname)s %(name)s: %(message)s"))
        _log.addHandler(handler)
        _log.setLevel(logging.INFO)
    from production_stack_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    if args.fault_injection is not None:
        # "" arms the live /debug/faults toggle with no faults injected
        os.environ["FAULT_INJECTION"] = args.fault_injection

    from production_stack_tpu.parallel.distributed import (
        DistributedConfig,
        initialize_distributed,
    )

    dist = DistributedConfig.from_env(
        coordinator=args.distributed_coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
        control_port=args.control_port,
    )
    if dist.enabled:
        from production_stack_tpu.engine.multihost import control_secret

        control_secret()  # fail fast: no secret, no multi-host
        if args.host_offload_blocks or args.remote_kv_url:
            raise SystemExit(
                "multi-host serving does not yet compose with the "
                "host-offload / remote-KV tiers (their device transfers "
                "run outside the mirrored runner)"
            )
        # must precede the first backend touch: afterwards jax.devices()
        # is the GLOBAL device list and one Mesh spans all hosts
        initialize_distributed(dist)
    config = config_from_args(args)
    # run_app installs its own SIGINT/SIGTERM handlers once the loop runs;
    # until then (engine construction) SIGTERM exits through SystemExit so
    # atexit hooks run. The chip itself needs no release call: libtpu frees
    # it when the process ends, however it ends.

    def _early_term(signum, frame):
        raise SystemExit(128 + signum)

    signal.signal(signal.SIGTERM, _early_term)

    if config.model.architecture == "whisper":
        # encoder-decoder transcription engine: its own runner + server
        # (whisper_server.py) — the paged text engine never starts.
        # B=1 per call saturates the MXU on the fixed 30 s window; shard
        # bigger models with --tensor-parallel-size, scale out replicas.
        if dist.enabled:
            raise SystemExit(
                "whisper serving is single-controller; scale with "
                "--tensor-parallel-size within one host or add replicas"
            )
        from production_stack_tpu.engine.whisper_server import (
            run_whisper_server,
        )

        run_whisper_server(config, args.host, args.port)
        return

    if dist.enabled and not dist.is_leader:
        _follower_main(config, dist, args.host, args.port)
        return

    import jax

    with start.span("backend_open"):
        jax.devices()  # opens the backend; fails here if the chip is held
    engine = LLMEngine(config, start=start)
    took = start.seconds()
    print(f"engine startup: {jax.default_backend()} x{jax.device_count()} "
          f"({jax.devices()[0].device_kind}), process {took['process']:.2f}s, "
          f"backend open {took['backend_open']:.2f}s, engine build "
          f"{took['engine_build']:.2f}s (tokenizer {took['tokenizer']:.2f}s "
          f"through {engine.tokenizer.loader}, weights "
          f"{took['weights.make']:.2f} + {took['weights.quantize']:.2f} + "
          f"{took['weights.lay_out']:.2f}s, KV pool {took['kv_pool']:.2f}s, "
          f"device drain {took['device_drain']:.2f}s)", flush=True)
    broadcaster = None
    if dist.enabled:
        from production_stack_tpu.engine.multihost import (
            LeaderBroadcaster,
            MirroredRunner,
        )

        broadcaster = LeaderBroadcaster(dist.control_port,
                                        dist.num_processes - 1)
        import logging

        logging.getLogger(__name__).info(
            "waiting for %d follower(s) on control port %d",
            dist.num_processes - 1, dist.control_port,
        )
        broadcaster.wait_for_followers()
        # every later runner call (warmup included) is mirrored
        engine.runner = MirroredRunner(engine.runner, broadcaster)
        atexit.register(broadcaster.close)
    server = EngineServer(config, engine=engine,
                          warmup_on_start=not args.skip_warmup,
                          overload_retry_after=args.overload_retry_after,
                          otel_endpoint=args.otel_endpoint,
                          otel_service_name=args.otel_service_name,
                          otel_secure=args.otel_secure,
                          flight_recorder_size=args.flight_recorder_size,
                          drain_deadline=args.drain_deadline,
                          watchdog_stall_seconds=args.watchdog_stall_seconds,
                          diagnostics=diagnostics_config_from_args(args),
                          brownout=brownout_from_args(args))
    # the real process drains on SIGTERM instead of dying mid-stream;
    # in-process test servers keep run_app semantics untouched
    server.drain_on_sigterm = True
    web.run_app(server.build_app(), host=args.host, port=args.port,
                access_log=None)
    if broadcaster is not None:
        broadcaster.close()
    if server.warmup_error is not None:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
