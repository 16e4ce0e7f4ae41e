"""The proxy hot path: parse → resolve model → filter endpoints → route →
failover loop → relay stream, with request-stats hooks and usage accounting.

Reference flow: route_general_request + process_request
(src/vllm_router/services/request_service/request.py:225-677); failover loop
request.py:597-660; hop-by-hop sanitization request.py:82-100; orchestrated
disaggregated prefill request.py:719-921; scale-to-zero 404-vs-503
request.py:533-552.
"""

from __future__ import annotations

import asyncio
import json
import re
import time
import uuid
from typing import AsyncIterator, Optional

import aiohttp
from aiohttp import web

from production_stack_tpu.flight_recorder import FlightRecorder
from production_stack_tpu.router import metrics as m
from production_stack_tpu.router.experimental import tracing
from production_stack_tpu.router.log import init_logger
from production_stack_tpu.router.protocols import EndpointInfo
from production_stack_tpu.router.resilience import (
    Resilience,
    ResilienceConfig,
    get_resilience,
)
from production_stack_tpu.router.routing import (
    DisaggregatedPrefillOrchestratedRouter,
    breaker_filter,
    drop_draining,
    get_routing_logic,
)
from production_stack_tpu.router.service_discovery import get_service_discovery
from production_stack_tpu.router.stats import (
    get_engine_stats_scraper,
    get_request_stats_monitor,
)
from production_stack_tpu.tenancy import (
    CANARY_HEADER,
    CANARY_TENANT,
    TENANT_HEADER,
    resolve_tenant,
)

logger = init_logger(__name__)


class _NullStatsMonitor:
    """Stats sink for canary-stamped probes. The prober records its own
    SLO observations (exactly one availability attempt per probe), and
    synthetic traffic must never steer routing load estimates, scale
    signals, or tenant usage — observe-only by construction."""

    def on_new_request(self, *a, **k):
        pass

    def on_request_response(self, *a, **k):
        pass

    def on_request_complete(self, *a, **k):
        pass

    def on_request_swapped(self, *a, **k):
        pass


_NULL_MONITOR = _NullStatsMonitor()


def _stats_monitor_for(request):
    """The real request-stats monitor, or the null sink for requests
    stamped ``x-canary: 1`` at admission."""
    if hasattr(request, "get") and request.get("canary"):
        return _NULL_MONITOR
    return get_request_stats_monitor()

HOP_BY_HOP = {
    "connection", "keep-alive", "proxy-authenticate", "proxy-authorization",
    "te", "trailers", "transfer-encoding", "upgrade", "host", "content-length",
}


def sanitize_headers(headers) -> dict[str, str]:
    return {k: v for k, v in headers.items() if k.lower() not in HOP_BY_HOP}


def _record_attempt(rec: Optional[dict], url: str,
                    t_start: float) -> Optional[dict]:
    """Append a backend-attempt entry to a flight record (None-safe)."""
    if rec is None:
        return None
    info = {"backend": url, "offset_s": round(time.time() - t_start, 6)}
    rec.setdefault("attempts", []).append(info)
    return info


def _mark_attempt(rec: Optional[dict], url: str, **fields) -> None:
    """Annotate the newest still-unresolved attempt entry for ``url``
    (hedged attempts resolve out of launch order)."""
    if rec is None:
        return
    for info in reversed(rec.get("attempts", [])):
        if info.get("backend") == url and "status" not in info \
                and "error" not in info:
            info.update(fields)
            return


def multipart_fields(raw: bytes, content_type: str,
                     names: tuple[str, ...]) -> dict[str, str]:
    """Extract small text fields from a multipart/form-data payload
    WITHOUT consuming an aiohttp stream: audio uploads must be relayed
    byte-identical to the backend (reference: request.py:1119-1143 there
    re-encodes the form; we forward the original bytes), but the router
    still needs `model` (routing) and `stream` (relay mode) up front."""
    marker = "boundary="
    i = content_type.find(marker)
    if i < 0:
        return {}
    boundary = content_type[i + len(marker):].split(";")[0].strip().strip('"')
    out: dict[str, str] = {}
    for part in raw.split(b"--" + boundary.encode()):
        head, sep, value = part.partition(b"\r\n\r\n")
        if not sep:
            continue
        for name in names:
            # `; name="x"` anchored on a delimiter: a file part whose
            # filename="model" must NOT match name="model" (r5 review)
            if re.search(rb'[;\s]name="%s"' % re.escape(name.encode()),
                         head):
                # the part body ends with exactly one CRLF before the
                # next boundary; trailing dashes are legitimate value
                # characters (model names can end with "-")
                if value.endswith(b"\r\n"):
                    value = value[:-2]
                out[name] = value.decode("utf-8", errors="replace")
    return out


# endpoint path → capability family an engine must advertise to receive it
# (reference surface: src/vllm_router/routers/main_router.py:51-301 — there
# every path is proxied blind and an incapable vLLM pod 404s mid-request;
# here engines advertise capabilities in /v1/models and the router refuses
# up front with a clean 501). Backends that don't advertise (capabilities
# None) are never filtered.
PATH_CAPABILITY = {
    "/v1/chat/completions": "chat",
    "/v1/completions": "completions",
    "/v1/embeddings": "embeddings",
    "/v1/rerank": "rerank",
    "/rerank": "rerank",
    "/v1/score": "score",
    "/score": "score",
    "/v1/responses": "responses",
    "/v1/messages": "messages",
    "/v1/audio/transcriptions": "audio.transcriptions",
    "/v1/audio/translations": "audio.translations",
    "/v1/audio/speech": "audio.speech",
    "/v1/images/generations": "images.generations",
    "/v1/images/edits": "images.edits",
    "/pooling": "pooling",
    "/classify": "classify",
}


class RequestService:
    """Bound to the router app; owns the shared backend client session."""

    def __init__(
        self,
        max_failover_attempts: int = 0,
        request_timeout: float = 600.0,
        model_aliases: Optional[dict[str, str]] = None,
        rewriter=None,
        callbacks=None,
        external_providers=None,
        resilience: Optional[Resilience] = None,
        flight_recorder: Optional[FlightRecorder] = None,
        tenant_header: str = TENANT_HEADER,
        quota=None,
        brownout=None,
    ):
        self.max_failover_attempts = max_failover_attempts
        self.request_timeout = request_timeout
        self.model_aliases = model_aliases or {}
        self.rewriter = rewriter
        self.callbacks = callbacks
        self.external_providers = external_providers
        self.post_response = None  # optional (body, response_tail) hook
        self._session: Optional[aiohttp.ClientSession] = None
        self._resilience = resilience
        # default keeps directly-constructed services (tests) working
        self.flight_recorder = flight_recorder or FlightRecorder()
        # inbound header the tenant identity is read from
        # (tenancy.resolve_tenant precedence: header > body "user" field >
        # API-key hash > "anonymous"); the resolved identity is stamped
        # onto every backend hop as the CANONICAL x-tenant-id so engine-
        # side attribution agrees with the router whatever header the
        # operator configured inbound
        self.tenant_header = tenant_header or TENANT_HEADER
        # per-tenant admission quotas (router/quota.py QuotaManager; None
        # = default-off). Checked right after resolve_tenant — the ONE
        # point every request passes exactly once, so under disagg the
        # P->D decode hop (engine-to-engine) can never double-charge.
        self.quota = quota
        # router-tier brownout ladder (engine/overload.py
        # BrownoutController; None = off). The app's eval worker drives
        # evaluate() and refreshes `brownout_shed` — the over-weight
        # tenant set stage 3 refuses new admissions from.
        self.brownout = brownout
        self.brownout_shed: set = set()

    @property
    def resilience(self) -> Resilience:
        if self._resilience is None:
            # late-bind the app singleton; default-config fallback keeps
            # directly-constructed services (tests) working
            self._resilience = get_resilience() or Resilience(ResilienceConfig())
        return self._resilience

    async def start(self) -> None:
        self._session = aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=self.request_timeout, sock_read=None)
        )

    async def stop(self) -> None:
        if self._session:
            await self._session.close()

    @property
    def session(self) -> aiohttp.ClientSession:
        assert self._session is not None, "request service not started"
        return self._session

    @staticmethod
    def _tenant_of(request) -> str:
        """The tenant resolved at admission (_route_general_request);
        empty for surfaces that never resolved one."""
        return (request.get("tenant") or "") if hasattr(request, "get") \
            else ""

    def _admission_check(self, tenant: str, body: dict,
                         rec: dict):
        """Per-tenant admission control (overload protection plane).

        Two independent gates, both default-off: the stage-3 brownout
        shed (over-weight tenants' NEW admissions refused while the
        ladder is at stage 3) and the token-bucket quota check. Returns
        a 429 response to short-circuit with, or None to admit. The 429
        carries Retry-After derived from the bucket's ACTUAL refill time
        so PR 1's breaker/backoff machinery paces clients proportionally
        to how far over quota they are."""
        if (self.brownout is not None and self.brownout.shed_overweight
                and tenant in self.brownout_shed):
            self.brownout.record_shed("tenant")
            rec["outcome"] = "brownout_shed"
            return web.json_response(
                {"error": {
                    "message": f"tenant {tenant!r} admissions shed: fleet "
                               "in brownout stage "
                               f"{self.brownout.stage} and this tenant is "
                               "over its fair-share weight; retry later",
                    "type": "RateLimitError", "code": "brownout_shed",
                }},
                status=429,
                headers={"Retry-After": f"{self.brownout.config.interval:g}"},
            )
        if self.quota is None:
            return None
        from production_stack_tpu.router.quota import estimate_tokens
        verdict = self.quota.check(tenant, estimate_tokens(body),
                                   time.monotonic())
        if verdict.allowed:
            return None
        m.refresh_quota_gauges(self.quota)
        rec["outcome"] = "over_quota"
        ra = max(verdict.retry_after, 0.05)
        return web.json_response(
            {"error": {
                "message": f"tenant {tenant!r} over its "
                           f"{'requests/s' if verdict.reason == 'rps' else 'tokens/s'}"
                           f" quota; retry after {ra:.2f}s",
                "type": "RateLimitError", "code": "over_quota",
            }},
            status=429,
            headers={"Retry-After": f"{ra:.2f}"},
        )

    # -- endpoint selection ---------------------------------------------------
    def _filter_endpoints(self, model: str) -> list[EndpointInfo]:
        eps = get_service_discovery().get_endpoint_info()
        eps = [e for e in eps if e.serves(model) and not e.sleep]
        # draining endpoints (engine shutting down, watchdog-stalled, or
        # pod stamped with a deletionTimestamp) keep their live streams
        # but take no NEW requests — unless their whole ROLE pool is
        # draining (single-replica rollout): then they stay listed,
        # because a draining engine still answers an honest 503 +
        # Retry-After that failover and clients can act on
        # (docs/resilience.md). Role-scoped so a fully-draining decode
        # pool can't re-enter next to healthy prefill engines
        # (routing.drop_draining).
        return drop_draining(eps)

    def resolve_model(self, model: str) -> str:
        return self.model_aliases.get(model, model)

    def _resume_state(self, endpoint_path: str, body: dict,
                      raw_body: Optional[bytes]) -> Optional["_ResumeState"]:
        """Arm resume-from-prefix replay when the request shape supports
        continuation semantics: a single streamed completion with a
        string prompt (or a chat message list). Echo/logprobs/suffix and
        n>1 are excluded — their outputs can't be spliced seamlessly."""
        if not self.resilience.config.stream_resume or raw_body is not None:
            return None
        if not body.get("stream", False):
            return None
        chat = endpoint_path == "/v1/chat/completions"
        if not chat and endpoint_path != "/v1/completions":
            return None
        if body.get("n") not in (None, 1):
            return None
        if any(body.get(k) for k in ("echo", "logprobs", "suffix",
                                     "top_logprobs")):
            return None
        if chat:
            if not isinstance(body.get("messages"), list) \
                    or not body["messages"]:
                return None
        elif not isinstance(body.get("prompt"), str):
            return None
        return _ResumeState(chat=chat)

    # -- the main proxy -------------------------------------------------------
    async def route_general_request(
        self, request: web.Request, endpoint_path: str
    ) -> web.StreamResponse:
        """Observability wrapper around the proxy hot path: opens the
        router SERVER span (joining any client trace), starts a flight
        record, and classifies the outcome — then delegates to
        :meth:`_route_general_request`, which does the actual routing."""
        t_start = time.time()
        request_id = (request.get("request_id")
                      if hasattr(request, "get") else None) \
            or request.headers.get("x-request-id") or str(uuid.uuid4())
        rec = self.flight_recorder.begin(
            request_id=request_id, endpoint=endpoint_path, model=None,
            trace_id=None, outcome=None, status=None,
        )
        try:
            request["flight_record"] = rec
        except TypeError:
            pass  # non-aiohttp mocks in unit tests
        inbound_ctx = tracing.extract_context(request.headers)
        span_cm = tracing.request_span(
            f"router {endpoint_path}",
            context=inbound_ctx,
            kind="server",
            attributes={"http.target": endpoint_path,
                        "request.id": request_id},
        )
        status: Optional[int] = None
        try:
            with span_cm as span:
                # current-span id when the SDK records spans; the inbound
                # context's id in API-only (propagation-only) mode
                rec["trace_id"] = (tracing.trace_id_hex()
                                   or tracing.trace_id_hex(inbound_ctx))
                resp = await self._route_general_request(
                    request, endpoint_path, request_id, t_start, rec
                )
                status = resp.status
                if span is not None:
                    span.set_attribute("http.status_code", status)
                return resp
        except asyncio.CancelledError:
            rec["outcome"] = "client_disconnect"
            raise
        finally:
            rec["status"] = status
            if rec.get("outcome") is None:
                if status is None:
                    rec["outcome"] = "error"
                elif status == 504:
                    rec["outcome"] = "deadline_exceeded"
                elif status < 400:
                    rec["outcome"] = "completed"
                else:
                    rec["outcome"] = "error"
            self.flight_recorder.finish(rec)

    async def _route_general_request(
        self, request: web.Request, endpoint_path: str, request_id: str,
        t_start: float, rec: dict,
    ) -> web.StreamResponse:
        raw_body: Optional[bytes] = None
        if request.content_type.startswith("multipart/"):
            # audio uploads: relay the original bytes; pull only the
            # routing fields out of the form. Callback/rewriter hooks are
            # JSON-body contracts and don't apply to multipart.
            raw_body = await request.read()
            fields = multipart_fields(
                raw_body, request.headers.get("Content-Type", ""),
                ("model", "stream"))
            body = {"model": fields.get("model", ""),
                    "stream": fields.get("stream", "").lower()
                    in ("true", "1")}
        else:
            try:
                body = await request.json()
            except Exception:
                return web.json_response(
                    {"error": {"message": "invalid JSON body"}}, status=400
                )

            if self.callbacks is not None:
                short = self.callbacks.pre_request(request, body)
                if short is not None:
                    return web.json_response(short)
            if self.rewriter is not None:
                body = self.rewriter.rewrite(endpoint_path, body)

        model = body.get("model", "")
        resolved = self.resolve_model(model)
        body["model"] = resolved
        rec["model"] = resolved
        # tenant identity for attribution, resolved once at admission and
        # carried on the request for every backend hop (observe-only).
        # Canary-stamped probes (router/canary.py) are forced onto the
        # reserved _canary tenant and bypass quotas/brownout shed: the
        # prober must observe the serving path, not the admission plane,
        # and its traffic may never debit a real tenant's bucket.
        canary = request.headers.get(CANARY_HEADER) == "1"
        if canary:
            request["canary"] = True
            rec["canary"] = True
            tenant = CANARY_TENANT
        else:
            tenant = resolve_tenant(request.headers, body,
                                    header_name=self.tenant_header)
        request["tenant"] = tenant
        rec["tenant"] = tenant
        m.num_incoming_requests_total.labels(model=resolved or "unknown").inc()

        shed = None if canary else self._admission_check(tenant, body, rec)
        if shed is not None:
            return shed

        if self.external_providers is not None and self.external_providers.handles(
            resolved
        ):
            if raw_body is not None:
                # the provider proxy re-serialises `body` as JSON — a
                # multipart upload would be silently dropped (r5 review)
                return web.json_response(
                    {"error": {
                        "message": f"model {resolved!r} is served by an "
                                   "external provider, which does not "
                                   "support multipart audio uploads",
                        "type": "NotImplementedError",
                        "code": "unsupported_endpoint",
                    }},
                    status=501,
                )
            return await self.external_providers.proxy(
                request, endpoint_path, body, resolved
            )

        endpoints = self._filter_endpoints(resolved)
        if not endpoints:
            discovery = get_service_discovery()
            if resolved in discovery.known_models:
                return web.json_response(
                    {"error": {"message": f"model {resolved!r} is scaled to zero "
                               "or sleeping; retry later"}},
                    status=503,
                )
            return web.json_response(
                {"error": {"message": f"model {resolved!r} not found",
                           "type": "NotFoundError"}},
                status=404,
            )

        capability = PATH_CAPABILITY.get(endpoint_path)
        capable = [e for e in endpoints if e.supports(capability)]
        if not capable:
            return web.json_response(
                {"error": {
                    "message": f"no backend serving {resolved!r} supports "
                               f"{endpoint_path} (requires the "
                               f"{capability!r} capability)",
                    "type": "NotImplementedError",
                    "code": "unsupported_endpoint",
                }},
                status=501,
            )
        endpoints = capable

        router = get_routing_logic()
        if (isinstance(router, DisaggregatedPrefillOrchestratedRouter)
                and raw_body is None):  # audio has no prefill/decode split
            return await self._orchestrated_disagg(
                request, endpoint_path, body, endpoints, router, request_id, t_start
            )

        engine_stats = get_engine_stats_scraper().get_engine_stats()
        request_stats = get_request_stats_monitor().get_request_stats()

        res = self.resilience
        deadline = self._request_deadline(request, t_start)
        res.budget.on_request()
        m.retry_budget_remaining.set(res.budget.remaining())

        if raw_body is None and not body.get("stream", False) \
                and len(endpoints) > 1:
            hedge_delay = res.hedge.delay()
            if hedge_delay is not None:
                return await self._hedged_request(
                    request, endpoint_path, body, endpoints, router,
                    engine_stats, request_stats, resolved, request_id,
                    t_start, deadline, hedge_delay,
                )

        resume = self._resume_state(endpoint_path, body, raw_body)
        attempts = 1 + max(self.max_failover_attempts, 0)
        failed: set[str] = set()
        last_error: Optional[str] = None
        give_up = "failed"
        for attempt in range(attempts):
            if attempt > 0:
                if deadline is not None and time.time() >= deadline:
                    last_error = ("deadline exceeded during failover: "
                                  f"{last_error}")
                    give_up = "deadline"
                    break
                if not res.budget.try_acquire():
                    logger.warning(
                        "retry budget exhausted; shedding retry of request "
                        "%s", request_id)
                    give_up = "budget_exhausted"
                    break
                m.retry_budget_remaining.set(res.budget.remaining())
            avail = [e for e in endpoints if e.url not in failed] or endpoints
            candidates = breaker_filter(avail)
            url = await router.route_request(
                candidates, engine_stats, request_stats,
                dict(request.headers), body,
            )
            res.breaker.on_attempt_start(url)
            logger.info("Routing request %s to %s (attempt %d)", request_id,
                        url, attempt + 1)
            try:
                resp = await self._proxy_and_stream(
                    request, endpoint_path, body, url, resolved, request_id,
                    t_start, raw_body=raw_body, deadline=deadline,
                    resume=resume,
                )
                if resume is not None and resume.resumed:
                    # every mid-stream death was spliced over seamlessly
                    m.stream_resumes_total.labels(outcome="resumed").inc(
                        resume.resumed)
                return resp
            except StreamInterrupted as e:
                # backend died with the client stream already prepared:
                # the next loop iteration replays from the generated
                # prefix (breaker already told in _attempt)
                last_error = str(e)
                failed.add(url)
                m.request_errors_total.labels(
                    server=url, model=resolved, error_type="stream_abort"
                ).inc()
                logger.warning(
                    "backend %s died mid-stream for request %s after %d "
                    "token(s) (%s); resuming from generated prefix", url,
                    request_id, e.state.completion_tokens(), e)
            except BackendError as e:
                last_error = str(e)
                failed.add(url)
                res.breaker.record_failure(url, e.kind,
                                           retry_after=e.retry_after)
                m.request_errors_total.labels(
                    server=url, model=resolved, error_type=e.kind
                ).inc()
                logger.warning(
                    "backend %s failed for request %s (%s); rerouting", url,
                    request_id, e,
                )
        if resume is not None and resume.resp is not None:
            # stream already prepared: a JSON error can't be sent, so
            # terminate in-band like the engine's deadline path does
            outcome = "failed" if give_up == "deadline" else give_up
            return await self._fail_resumed_stream(resume, last_error,
                                                   outcome, url=url,
                                                   model=resolved)
        if give_up == "deadline":
            return web.json_response(
                {"error": {"message": last_error}}, status=504)
        return web.json_response(
            {"error": {"message": f"all backends failed: {last_error}"}}, status=503
        )

    async def _fail_resumed_stream(self, resume: "_ResumeState",
                                   last_error: Optional[str],
                                   outcome: str,
                                   url: Optional[str] = None,
                                   model: Optional[str] = None,
                                   ) -> web.StreamResponse:
        """Every replay avenue is gone (no surviving backend, deadline,
        or retry budget) with the client mid-stream: send an in-band
        error event and a clean [DONE] instead of a raw connection
        reset, and record the loss."""
        m.stream_resumes_total.labels(outcome=outcome).inc()
        from production_stack_tpu.router.incidents import (
            current_incident_manager,
        )

        im = current_incident_manager()
        if im is not None:
            # the client saw a lost stream: open (and record) an incident
            im.on_stream_resume_failure(outcome, url, model)
        err = {"error": {"message": "stream interrupted and could not be "
                         f"resumed: {last_error}",
                         "type": "stream_resume_error"}}
        resp = resume.resp
        try:
            await resp.write(f"data: {json.dumps(err)}\n\n".encode())
            await resp.write(b"data: [DONE]\n\n")
            await resp.write_eof()
        except (ConnectionResetError, aiohttp.ClientError):
            pass  # client is gone too; nothing left to salvage
        return resp

    def _request_deadline(self, request: web.Request,
                          t_start: float) -> Optional[float]:
        """Absolute epoch deadline propagated to engines: min of a
        client-supplied ``x-request-deadline`` and the router timeout."""
        if not self.resilience.config.deadline_propagation:
            return None
        deadline = t_start + self.request_timeout
        hdr = request.headers.get("x-request-deadline")
        if hdr:
            try:
                deadline = min(deadline, float(hdr))
            except ValueError:
                logger.warning("ignoring malformed x-request-deadline %r", hdr)
        return deadline

    # -- hedged requests ------------------------------------------------------
    async def _hedged_request(
        self, request, endpoint_path, body, endpoints, router, engine_stats,
        request_stats, model, request_id, t_start, deadline, hedge_delay,
    ) -> web.StreamResponse:
        """Race a primary attempt against a delayed hedge on a different
        backend; first success wins, the loser is cancelled. Buffered
        (non-streaming) only — a prepared stream cannot be discarded.
        Hedges and failover replacements both draw from the retry budget."""
        res = self.resilience
        failed: set[str] = set()
        tasks: dict[asyncio.Task, str] = {}
        last_error: Optional[str] = None
        extra_attempts = max(self.max_failover_attempts, 0)
        rec = request.get("flight_record") if hasattr(request, "get") else None

        async def launch(exclude: set[str]) -> None:
            avail = [e for e in endpoints
                     if e.url not in failed and e.url not in exclude]
            avail = avail or [e for e in endpoints if e.url not in failed] \
                or endpoints
            candidates = breaker_filter(avail)
            url = await router.route_request(
                candidates, engine_stats, request_stats,
                dict(request.headers), body,
            )
            res.breaker.on_attempt_start(url)
            logger.info("Routing request %s to %s (hedged, %d in flight)",
                        request_id, url, len(tasks))
            _record_attempt(rec, url, t_start)
            tasks[asyncio.ensure_future(self._buffered_attempt(
                request, endpoint_path, body, url, model, request_id,
                t_start, deadline))] = url

        try:
            await launch(set())
            hedged = False
            while tasks:
                timeout = None
                if not hedged:
                    elapsed = time.time() - t_start
                    timeout = max(0.0, hedge_delay - elapsed)
                done, _ = await asyncio.wait(
                    tasks, timeout=timeout,
                    return_when=asyncio.FIRST_COMPLETED)
                if not done:
                    # hedge timer fired with the primary still in flight
                    hedged = True
                    in_flight = set(tasks.values())
                    others = [e for e in endpoints
                              if e.url not in in_flight | failed]
                    if others and res.budget.try_acquire():
                        m.hedged_requests_total.inc()
                        m.retry_budget_remaining.set(res.budget.remaining())
                        await launch(in_flight)
                    continue
                for t in done:
                    url = tasks.pop(t)
                    try:
                        resp = t.result()
                        _mark_attempt(rec, url, status=resp.status)
                        return resp
                    except BackendError as e:
                        _mark_attempt(rec, url, error=e.kind)
                        last_error = str(e)
                        failed.add(url)
                        res.breaker.record_failure(
                            url, e.kind, retry_after=e.retry_after)
                        m.request_errors_total.labels(
                            server=url, model=model, error_type=e.kind
                        ).inc()
                        logger.warning(
                            "backend %s failed for request %s (%s); hedge "
                            "race continues", url, request_id, e)
                if not tasks and extra_attempts > 0:
                    if deadline is not None and time.time() >= deadline:
                        return web.json_response(
                            {"error": {"message": "deadline exceeded during "
                                       f"failover: {last_error}"}}, status=504)
                    if not res.budget.try_acquire():
                        logger.warning("retry budget exhausted; shedding "
                                       "retry of request %s", request_id)
                        break
                    m.retry_budget_remaining.set(res.budget.remaining())
                    extra_attempts -= 1
                    await launch(set())
            return web.json_response(
                {"error": {"message": f"all backends failed: {last_error}"}},
                status=503)
        finally:
            for t in tasks:  # cancel the losing attempt(s)
                if t.done():
                    t.exception()  # consume, avoid "never retrieved" noise
                else:
                    t.cancel()

    async def _proxy_and_stream(
        self, request, endpoint_path, body, url, model, request_id, t_start,
        raw_body: Optional[bytes] = None, deadline: Optional[float] = None,
        resume: Optional["_ResumeState"] = None,
    ) -> web.StreamResponse:
        """One backend attempt. Raises BackendError before any byte has been
        relayed (so failover is safe); after first byte, errors terminate the
        stream — unless ``resume`` is armed, in which case a mid-stream death
        raises StreamInterrupted carrying the prepared response and generated
        prefix so the failover loop can replay the remainder. ``raw_body``
        (multipart audio) is relayed byte-identical instead of re-serialising
        ``body``."""
        monitor = _stats_monitor_for(request)
        stream = bool(body.get("stream", False))
        strip_usage = False
        strip_chunk_usage = False
        if stream and raw_body is None:
            # ask the engine for the final usage chunk so streamed requests
            # feed token accounting; if the client didn't request it, the
            # chunk is stripped from the relayed stream (OpenAI parity)
            so = body.get("stream_options")
            so = so if isinstance(so, dict) else {}
            inject = {}
            if not so.get("include_usage"):
                inject["include_usage"] = True
                strip_usage = True
            if resume is not None and not so.get("continuous_usage_stats"):
                # per-chunk cumulative usage keeps the resume accounting
                # token-exact (one SSE event can carry several tokens);
                # the injected field is stripped before relay
                inject["continuous_usage_stats"] = True
                strip_chunk_usage = True
            if inject:
                body = {**body, "stream_options": {**so, **inject}}
        tenant = self._tenant_of(request)
        monitor.on_new_request(url, request_id, time.time(), model=model,
                               tenant=tenant)
        headers = sanitize_headers(request.headers)
        headers["x-request-id"] = request_id
        if tenant:
            headers[TENANT_HEADER] = tenant
        if deadline is not None:
            headers["x-request-deadline"] = f"{deadline:.3f}"
        # CLIENT span per backend attempt, child of the router SERVER span
        # opened in route_general_request (which already joined any client
        # traceparent); the W3C context continues into the engine so its
        # spans/logs join the same trace
        span_cm = tracing.request_span(
            f"backend {endpoint_path}",
            kind="client",
            attributes={"backend.url": url, "model": model,
                        "request.id": request_id, "streaming": stream},
        )
        span_cm.__enter__()
        tracing.inject_headers(headers)
        rec = request.get("flight_record") if hasattr(request, "get") else None
        attempt_info = _record_attempt(rec, url, t_start)
        # the wall-clock instant of this forward: the engine's record
        # keeps it beside its own `received_unix`, and the difference is
        # the hop plus the wait for the engine's handler (exact on one
        # host; across nodes it carries their clock skew)
        headers["x-router-sent-unix"] = repr(time.time())
        try:
            resp = await self._attempt(
                request, endpoint_path, body, url, model, request_id, t_start,
                monitor, stream, headers, span_cm, strip_usage=strip_usage,
                strip_chunk_usage=strip_chunk_usage,
                raw_body=raw_body, resume=resume,
            )
            if attempt_info is not None:
                attempt_info["status"] = resp.status
            return resp
        except BackendError as e:
            if attempt_info is not None:
                attempt_info["error"] = e.kind
            raise
        except StreamInterrupted:
            if attempt_info is not None:
                attempt_info["error"] = "stream_abort"
            raise
        finally:
            span_cm.__exit__(None, None, None)

    async def _attempt(self, request, endpoint_path, body, url, model,
                       request_id, t_start, monitor, stream, headers,
                       span_cm, strip_usage=False, strip_chunk_usage=False,
                       raw_body: Optional[bytes] = None,
                       resume: Optional["_ResumeState"] = None,
                       ) -> web.StreamResponse:
        is_continuation = resume is not None and resume.resp is not None
        if is_continuation:
            # replay: everything relayed so far becomes prompt prefix
            body = _continuation_body(body, resume)
            resume.start_attempt()
        try:
            if raw_body is not None:  # multipart: original bytes + boundary
                backend = await self.session.post(
                    f"{url}{endpoint_path}", data=raw_body, headers=headers
                )
            else:
                backend = await self.session.post(
                    f"{url}{endpoint_path}", json=body, headers=headers
                )
        except (aiohttp.ClientError, asyncio.TimeoutError) as e:
            monitor.on_request_complete(url, request_id, time.time())
            raise BackendError("connect", f"{type(e).__name__}: {e}") from e

        if backend.status >= 500:
            try:
                text = await backend.text()
            except aiohttp.ClientError:
                text = "<unreadable body>"
            finally:
                backend.release()
                monitor.on_request_complete(url, request_id, time.time())
            raise BackendError("http_5xx", f"HTTP {backend.status}: {text[:200]}")

        retry_after = _overload_retry_after(backend)
        if retry_after is not None:
            # honest overload signal: fail over elsewhere and let the
            # breaker throttle this backend for Retry-After seconds
            try:
                text = await backend.text()
            except aiohttp.ClientError:
                text = "<unreadable body>"
            finally:
                backend.release()
                monitor.on_request_complete(url, request_id, time.time())
            raise BackendError("overload", f"HTTP 429: {text[:200]}",
                               retry_after=retry_after)

        self.resilience.breaker.record_success(url, time.time() - t_start)
        if is_continuation:
            # splice into the client response prepared by the attempt
            # that died; the continuation backend's status/headers are
            # consumed here, never seen by the client
            resume.resumed += 1
            resp = resume.resp
        else:
            resp = web.StreamResponse(
                status=backend.status,
                headers={
                    **sanitize_headers(backend.headers),
                    "x-request-id": request_id,
                },
            )
        first = True
        n_output_tokens = 0
        buffer = b""
        status_label = str(backend.status)
        strip = (strip_usage and backend.status == 200
                 and backend.headers.get("Content-Type", "")
                 .startswith("text/event-stream"))
        # event-split relay when stripping usage OR accumulating resume
        # state; writing event+sep back is byte-preserving, so the happy
        # path stays bit-identical to a raw relay
        use_events = strip or (resume is not None and backend.status == 200)
        pending = b""
        try:
            if not is_continuation:
                await resp.prepare(request)
                if resume is not None and backend.status == 200:
                    # from here on a backend death can't fail over — it
                    # must resume into this prepared response
                    resume.resp = resp
            async for chunk in backend.content.iter_any():
                if first:
                    monitor.on_request_response(url, request_id, time.time())
                    first = False
                buffer = (buffer + chunk)[-65536:]  # tail only, usage lives there
                if not use_events:
                    await resp.write(chunk)
                    continue
                # SSE-event-aware relay: drop the router-injected usage-only
                # chunk the client didn't ask for, fold events into the
                # resume accumulator, rewrite continuation events to look
                # like the original stream
                pending += chunk
                while True:
                    event, sep, rest = _split_sse_event(pending)
                    if sep is None:
                        break
                    pending = rest
                    if resume is not None:
                        resume.observe(event)
                    if strip and _is_usage_only_event(event):
                        continue
                    if strip_chunk_usage:
                        event = _strip_inline_usage(event)
                    if is_continuation:
                        # the continuation opens its own stream: drop its
                        # fresh role delta (the client already got one)
                        # and make its events look like the original's
                        if resume.chat and _is_role_only_event(event):
                            continue
                        event = resume.rewrite(event)
                    await resp.write(event + sep)
            if pending:
                await resp.write(pending)
            await resp.write_eof()
        except aiohttp.ClientError as e:
            # backend died mid-stream (e.g. stream_abort_rate fault); the
            # client already got bytes so a clean failover is impossible,
            # but with resume armed the failover loop can replay from the
            # generated prefix. Either way the breaker should know.
            status_label = "stream_abort"
            self.resilience.breaker.record_failure(url, "stream_abort")
            if resume is not None and resume.resp is not None \
                    and not resume.finished:
                raise StreamInterrupted(
                    resume, f"{type(e).__name__}: {e}") from e
            raise
        except (ConnectionResetError, asyncio.CancelledError):
            status_label = "client_disconnect"
            raise
        finally:
            usage = _extract_usage(buffer, stream)
            if usage:
                n_output_tokens = usage.get("completion_tokens", 0) or 0
                m.input_tokens_total.labels(server=url, model=model).inc(
                    usage.get("prompt_tokens", 0) or 0
                )
                m.output_tokens_total.labels(server=url, model=model).inc(
                    n_output_tokens
                )
            now = time.time()
            monitor.on_request_complete(url, request_id, now, n_output_tokens)
            m.request_latency_seconds.labels(
                server=url, model=model, status=status_label
            ).observe(now - t_start)
            backend.release()
            if span_cm.span is not None:
                span_cm.span.set_attribute("http.status_code", backend.status)
            if status_label == "200":
                if not stream:  # hedge delay tracks full-response p95
                    self.resilience.hedge.observe(now - t_start)
                if self.post_response is not None and not stream:
                    try:
                        self.post_response(body, buffer)
                    except Exception as e:
                        logger.warning("post_response hook failed: %s", e)
                if self.callbacks is not None:
                    self.callbacks.post_request(request, body, buffer)
        return resp

    async def _buffered_attempt(self, request, endpoint_path, body, url,
                                model, request_id, t_start,
                                deadline: Optional[float] = None,
                                ) -> web.Response:
        """One fully-buffered backend attempt for the hedging path: a
        buffered response can be discarded when the other attempt wins,
        a prepared StreamResponse cannot. Raises BackendError on connect
        failure / 5xx / overload-429, mirroring ``_attempt``'s contract,
        and keeps the same stats/usage accounting."""
        monitor = _stats_monitor_for(request)
        res = self.resilience
        tenant = self._tenant_of(request)
        headers = sanitize_headers(request.headers)
        headers["x-request-id"] = request_id
        if tenant:
            headers[TENANT_HEADER] = tenant
        if deadline is not None:
            headers["x-request-deadline"] = f"{deadline:.3f}"
        monitor.on_new_request(url, request_id, time.time(), model=model,
                               tenant=tenant)
        try:
            backend = await self.session.post(
                f"{url}{endpoint_path}", json=body, headers=headers
            )
        except (aiohttp.ClientError, asyncio.TimeoutError) as e:
            monitor.on_request_complete(url, request_id, time.time())
            raise BackendError("connect", f"{type(e).__name__}: {e}") from e

        try:
            if backend.status >= 500:
                try:
                    text = await backend.text()
                except aiohttp.ClientError:
                    text = "<unreadable body>"
                monitor.on_request_complete(url, request_id, time.time())
                raise BackendError("http_5xx",
                                   f"HTTP {backend.status}: {text[:200]}")
            retry_after = _overload_retry_after(backend)
            if retry_after is not None:
                try:
                    text = await backend.text()
                except aiohttp.ClientError:
                    text = "<unreadable body>"
                monitor.on_request_complete(url, request_id, time.time())
                raise BackendError("overload", f"HTTP 429: {text[:200]}",
                                   retry_after=retry_after)
            try:
                monitor.on_request_response(url, request_id, time.time())
                payload = await backend.read()
            except (aiohttp.ClientError, asyncio.TimeoutError) as e:
                monitor.on_request_complete(url, request_id, time.time())
                raise BackendError("read",
                                   f"{type(e).__name__}: {e}") from e
        finally:
            backend.release()

        now = time.time()
        res.breaker.record_success(url, now - t_start)
        res.hedge.observe(now - t_start)
        n_output_tokens = 0
        usage = _extract_usage(payload[-65536:], False)
        if usage:
            n_output_tokens = usage.get("completion_tokens", 0) or 0
            m.input_tokens_total.labels(server=url, model=model).inc(
                usage.get("prompt_tokens", 0) or 0
            )
            m.output_tokens_total.labels(server=url, model=model).inc(
                n_output_tokens
            )
        monitor.on_request_complete(url, request_id, now, n_output_tokens)
        m.request_latency_seconds.labels(
            server=url, model=model, status=str(backend.status)
        ).observe(now - t_start)
        if backend.status == 200:
            if self.post_response is not None:
                try:
                    self.post_response(body, payload[-65536:])
                except Exception as e:
                    logger.warning("post_response hook failed: %s", e)
            if self.callbacks is not None:
                self.callbacks.post_request(request, body, payload[-65536:])
        return web.Response(
            body=payload,
            status=backend.status,
            headers={**sanitize_headers(backend.headers),
                     "x-request-id": request_id},
        )

    # -- orchestrated disaggregated prefill -----------------------------------
    async def _orchestrated_disagg(
        self, request, endpoint_path, body, endpoints, router, request_id, t_start
    ) -> web.StreamResponse:
        """Single client call; router drives prefill then decode. KV moves
        prefill→decode out-of-band, keyed by kv_transfer_params (our engines
        implement the transfer in engine/kv_transfer.py; the reference
        delegates to NIXL/LMCache). Two shapes:

        - streamed + resume-capable: the prefill hop runs buffered with
          max_tokens=1 and a push directive; the prefill engine streams its
          paged KV blocks straight into the decode engine's /kv/recv while
          the router relays the first token as synthesized SSE. The decode
          hop is then a continuation attempt (PR-7 resume machinery) that
          the decode engine satisfies by splicing the pushed blocks, by
          pulling from the prefill engine, or by re-prefilling the
          continuation prompt — bit-identical under greedy sampling either
          way. A decode death mid-stream replays on another decode backend.
        - everything else: the buffered pull flow (prefill returns block
          handles; decode pulls via /kv/export before admission).
        """
        engine_stats = get_engine_stats_scraper().get_engine_stats()
        request_stats = get_request_stats_monitor().get_request_stats()
        model = body.get("model", "")
        resume = self._resume_state(endpoint_path, body, None)
        if resume is not None:
            return await self._disagg_streamed(
                request, endpoint_path, body, endpoints, router, request_id,
                t_start, resume, engine_stats, request_stats, model)

        prefill_url, decode_url = await router.select_pair(
            endpoints, engine_stats, request_stats, dict(request.headers), body
        )
        if prefill_url is None:
            m.disagg_requests_total.labels(outcome="unified_fallback").inc()
            return await self._proxy_and_stream(
                request, endpoint_path, body, decode_url, model,
                request_id, t_start,
            )

        monitor = _stats_monitor_for(request)
        prefill_body = dict(body)
        prefill_body.update(
            {
                "max_tokens": 1, "max_completion_tokens": 1, "stream": False,
                "kv_transfer_params": {
                    "do_remote_decode": True,
                    "do_remote_prefill": False,
                    "remote_engine_id": None,
                    "remote_block_ids": None,
                    "remote_host": None,
                    "remote_port": None,
                },
            }
        )
        tenant = self._tenant_of(request)
        headers = sanitize_headers(request.headers)
        headers["x-request-id"] = request_id
        if tenant:
            headers[TENANT_HEADER] = tenant
        monitor.on_new_request(prefill_url, request_id, time.time(),
                               model=model, tenant=tenant)
        try:
            async with self.session.post(
                f"{prefill_url}{endpoint_path}", json=prefill_body, headers=headers
            ) as pre:
                pre_data = await pre.json()
                if pre.status != 200:
                    raise BackendError("prefill", f"HTTP {pre.status}: {pre_data}")
        except (aiohttp.ClientError, asyncio.TimeoutError, BackendError) as e:
            # the whole prompt is still in hand: serve unified off the
            # decode engine rather than failing the request
            logger.warning("prefill hop to %s failed for request %s (%s); "
                           "serving unified", prefill_url, request_id, e)
            m.request_errors_total.labels(
                server=prefill_url, model=model, error_type="prefill").inc()
            m.disagg_requests_total.labels(outcome="unified_fallback").inc()
            return await self._proxy_and_stream(
                request, endpoint_path, body, decode_url, model,
                request_id, t_start,
            )
        finally:
            monitor.on_request_complete(prefill_url, request_id, time.time())

        kv_params = pre_data.get("kv_transfer_params") or {}
        if not kv_params.get("remote_host"):
            kv_params["remote_host"] = prefill_url
        decode_body = dict(body)
        decode_body["kv_transfer_params"] = kv_params
        logger.info(
            "Routing request %s: prefill=%s decode=%s", request_id, prefill_url,
            decode_url,
        )
        resp = await self._proxy_and_stream(
            request, endpoint_path, decode_body, decode_url,
            model, request_id, t_start,
        )
        m.disagg_requests_total.labels(
            outcome="ok" if resp.status < 400 else "failed").inc()
        return resp

    async def _disagg_streamed(
        self, request, endpoint_path, body, endpoints, router, request_id,
        t_start, resume: "_ResumeState", engine_stats, request_stats,
        model: str,
    ) -> web.StreamResponse:
        """Streamed orchestrated disaggregation with a pushed KV handoff.

        Prefill hop: buffered, max_tokens=1, carrying a push directive
        {push_url, transfer_id} so the prefill engine streams KV into the
        chosen decode engine's /kv/recv before responding; fails over
        across the prefill pool, and degrades to a unified single-engine
        request when the pool is gone. First token: relayed to the client
        as synthesized SSE events (stamped with the prefill response's
        id, folded into the resume accumulator). Decode hop: a
        continuation attempt against the decode pool — the transfer_id
        lets the decode engine splice the pushed blocks and skip
        re-prefill; remote_block_ids/remote_host are the pull fallback;
        the continuation prompt itself is the re-prefill fallback. All
        three produce the same greedy completion."""
        res = self.resilience
        monitor = _stats_monitor_for(request)
        deadline = self._request_deadline(request, t_start)
        res.budget.on_request()
        m.retry_budget_remaining.set(res.budget.remaining())
        tenant = self._tenant_of(request)
        headers = sanitize_headers(request.headers)
        headers["x-request-id"] = request_id
        if tenant:
            headers[TENANT_HEADER] = tenant
        if deadline is not None:
            headers["x-request-deadline"] = f"{deadline:.3f}"
        transfer_id = str(uuid.uuid4())
        attempts = 1 + max(self.max_failover_attempts, 0)

        # ---- prefill hop, with failover across the prefill pool --------
        pre_data = None
        prefill_url: Optional[str] = None
        decode_url: Optional[str] = None
        p_failed: set[str] = set()
        last_error: Optional[str] = None
        for attempt in range(attempts):
            if attempt > 0:
                if deadline is not None and time.time() >= deadline:
                    break
                if not res.budget.try_acquire():
                    break
                m.retry_budget_remaining.set(res.budget.remaining())
            avail = [e for e in endpoints if e.url not in p_failed]
            p_url, d_url = await router.select_pair(
                breaker_filter(avail), engine_stats, request_stats,
                dict(request.headers), body)
            decode_url = d_url
            if p_url is None:
                break  # no (surviving) prefill pool → serve unified
            prefill_body = dict(body)
            prefill_body.update({
                "max_tokens": 1, "max_completion_tokens": 1, "stream": False,
                "kv_transfer_params": {
                    "do_remote_decode": True,
                    "do_remote_prefill": False,
                    "push_url": d_url,
                    "transfer_id": transfer_id,
                    "remote_engine_id": None,
                    "remote_block_ids": None,
                    "remote_host": None,
                    "remote_port": None,
                },
            })
            res.breaker.on_attempt_start(p_url)
            monitor.on_new_request(p_url, request_id, time.time(),
                                   model=model, tenant=tenant)
            _record_attempt(request.get("flight_record")
                            if hasattr(request, "get") else None,
                            p_url, t_start)
            try:
                async with self.session.post(
                    f"{p_url}{endpoint_path}", json=prefill_body,
                    headers=headers,
                ) as pre:
                    if pre.status != 200:
                        text = await pre.text()
                        raise BackendError(
                            "prefill", f"HTTP {pre.status}: {text[:200]}")
                    pre_data = await pre.json()
                res.breaker.record_success(p_url, time.time() - t_start)
                prefill_url = p_url
                break
            except (aiohttp.ClientError, asyncio.TimeoutError) as e:
                last_error = f"{type(e).__name__}: {e}"
                kind = "connect"
            except BackendError as e:
                last_error = str(e)
                kind = e.kind
            finally:
                monitor.on_request_complete(p_url, request_id, time.time())
            p_failed.add(p_url)
            res.breaker.record_failure(p_url, kind)
            m.request_errors_total.labels(
                server=p_url, model=model, error_type=kind).inc()
            logger.warning("prefill hop to %s failed for request %s (%s)",
                           p_url, request_id, last_error)

        if pre_data is None:
            # prefill pool empty or exhausted: one engine serves the whole
            # request (resume still armed — mid-stream deaths replay)
            m.disagg_requests_total.labels(outcome="unified_fallback").inc()
            url = decode_url or await router.route_request(
                breaker_filter(endpoints), engine_stats, request_stats,
                dict(request.headers), body)
            try:
                return await self._proxy_and_stream(
                    request, endpoint_path, body, url, model, request_id,
                    t_start, deadline=deadline, resume=resume)
            except StreamInterrupted as e:
                return await self._fail_resumed_stream(
                    resume, str(e), "failed", url=url, model=model)
            except BackendError as e:
                return web.json_response(
                    {"error": {"message": f"all backends failed: {e}"}},
                    status=503)

        # ---- relay the first token from the prefill response ------------
        kv_params = pre_data.get("kv_transfer_params") or {}
        if not kv_params.get("remote_host"):
            kv_params["remote_host"] = prefill_url
        logger.info(
            "Routing request %s: prefill=%s decode=%s transfer=%s pushed=%s",
            request_id, prefill_url, decode_url, transfer_id,
            kv_params.get("pushed"),
        )
        resp = web.StreamResponse(
            status=200,
            headers={"Content-Type": "text/event-stream",
                     "Cache-Control": "no-cache",
                     "x-request-id": request_id},
        )
        await resp.prepare(request)
        resume.resp = resp
        usage = pre_data.get("usage") or {}
        if isinstance(usage.get("prompt_tokens"), int):
            resume.prompt_tokens = usage["prompt_tokens"]
        for ev in _synth_first_events(pre_data, resume.chat):
            resume.observe(ev)
            await resp.write(ev + b"\n\n")

        finish = (pre_data.get("choices") or [{}])[0].get("finish_reason")
        requested = next((body[k] for k in ("max_tokens",
                                            "max_completion_tokens")
                          if isinstance(body.get(k), int)), None)
        if finish == "stop" or requested == 1:
            # the first token finished the completion (EOS, or the client
            # only asked for one token): no decode hop to run
            await self._finish_synth_stream(resp, pre_data, resume, body)
            m.disagg_requests_total.labels(outcome="ok").inc()
            return resp

        # ---- decode hop: continuation attempts over the decode pool ----
        decode_body = dict(body)
        decode_body["kv_transfer_params"] = {
            "do_remote_prefill": True,
            "transfer_id": transfer_id,
            "remote_engine_id": kv_params.get("remote_engine_id"),
            "remote_block_ids": kv_params.get("remote_block_ids"),
            "remote_host": kv_params.get("remote_host"),
            "remote_port": kv_params.get("remote_port"),
        }
        d_failed: set[str] = set()
        give_up = "failed"
        url: Optional[str] = None
        for attempt in range(attempts):
            if attempt > 0:
                if deadline is not None and time.time() >= deadline:
                    last_error = ("deadline exceeded during failover: "
                                  f"{last_error}")
                    give_up = "deadline"
                    break
                if not res.budget.try_acquire():
                    logger.warning("retry budget exhausted; shedding retry "
                                   "of request %s", request_id)
                    give_up = "budget_exhausted"
                    break
                m.retry_budget_remaining.set(res.budget.remaining())
            _, decode_pool = router.find_pools(endpoints)
            # prefer surviving decode engines; a drained decode pool falls
            # back to ANY engine (incl. prefill) — the continuation prompt
            # makes the request servable anywhere
            avail = [e for e in decode_pool if e.url not in d_failed] \
                or [e for e in endpoints if e.url not in d_failed]
            if not avail:
                break
            if decode_url is not None and decode_url not in d_failed \
                    and any(e.url == decode_url for e in avail):
                # the KV was pushed there — splice affinity beats load
                # balance (any other pick re-prefills and strands the
                # transfer until the decode engine's TTL sweep)
                url = decode_url
            else:
                url = await router.route_request(
                    breaker_filter(avail), engine_stats, request_stats,
                    dict(request.headers), body)
            res.breaker.on_attempt_start(url)
            try:
                out = await self._proxy_and_stream(
                    request, endpoint_path, decode_body, url, model,
                    request_id, t_start, deadline=deadline, resume=resume)
                m.disagg_requests_total.labels(
                    outcome="replayed" if resume.resumed > 1 else "ok").inc()
                if resume.resumed > 1:
                    # the by-design first continuation isn't a resume; only
                    # mid-stream replacements count as such
                    m.stream_resumes_total.labels(outcome="resumed").inc(
                        resume.resumed - 1)
                return out
            except StreamInterrupted as e:
                last_error = str(e)
                d_failed.add(url)
                m.request_errors_total.labels(
                    server=url, model=model, error_type="stream_abort").inc()
                logger.warning(
                    "decode backend %s died mid-stream for request %s after "
                    "%d token(s) (%s); resuming from generated prefix", url,
                    request_id, e.state.completion_tokens(), e)
            except BackendError as e:
                last_error = str(e)
                d_failed.add(url)
                res.breaker.record_failure(url, e.kind,
                                           retry_after=e.retry_after)
                m.request_errors_total.labels(
                    server=url, model=model, error_type=e.kind).inc()
                logger.warning(
                    "decode backend %s failed for request %s (%s); "
                    "rerouting", url, request_id, e)
        m.disagg_requests_total.labels(outcome="failed").inc()
        outcome = "failed" if give_up == "deadline" else give_up
        return await self._fail_resumed_stream(resume, last_error, outcome,
                                               url=url, model=model)

    async def _finish_synth_stream(self, resp, pre_data: dict,
                                   resume: "_ResumeState",
                                   body: dict) -> None:
        """Close a disagg stream that ended at the first token: finish
        chunk, the usage chunk if the client asked for one, [DONE]."""
        rid = pre_data.get("id")
        created = pre_data.get("created")
        model = pre_data.get("model")
        obj = "chat.completion.chunk" if resume.chat else "text_completion"
        finish = ((pre_data.get("choices") or [{}])[0].get("finish_reason")
                  or "length")
        if resume.chat:
            choice = {"index": 0, "delta": {}, "finish_reason": finish}
        else:
            choice = {"index": 0, "text": "", "logprobs": None,
                      "finish_reason": finish}
        await resp.write(b"data: " + json.dumps(
            {"id": rid, "object": obj, "created": created, "model": model,
             "choices": [choice]}).encode() + b"\n\n")
        so = body.get("stream_options")
        if isinstance(so, dict) and so.get("include_usage") \
                and pre_data.get("usage"):
            await resp.write(b"data: " + json.dumps(
                {"id": rid, "object": obj, "created": created,
                 "model": model, "choices": [],
                 "usage": pre_data["usage"]}).encode() + b"\n\n")
        await resp.write(b"data: [DONE]\n\n")
        await resp.write_eof()

    # -- sleep / wake proxying (reference: request.py:1027-1114) -------------
    async def sleep_wake(self, request: web.Request, action: str) -> web.Response:
        url = request.query.get("url") or request.rel_url.query.get("endpoint")
        eps = get_service_discovery().get_endpoint_info()
        targets = [e.url for e in eps if url is None or e.url == url]
        if not targets:
            return web.json_response({"error": {"message": "no endpoints"}}, status=404)
        results = {}
        for t in targets:
            try:
                if action == "is_sleeping":
                    async with self.session.get(f"{t}/is_sleeping") as r:
                        results[t] = await r.json()
                else:
                    async with self.session.post(
                        f"{t}/{action}", params=dict(request.query)
                    ) as r:
                        results[t] = await r.json()
                discovery = get_service_discovery()
                if action in ("sleep", "wake_up") and hasattr(discovery, "set_sleep"):
                    discovery.set_sleep(t, action == "sleep")
            except Exception as e:
                results[t] = {"error": str(e)}
        return web.json_response(results)


class BackendError(Exception):
    def __init__(self, kind: str, msg: str,
                 retry_after: Optional[float] = None):
        super().__init__(msg)
        self.kind = kind
        #: backend-requested back-off (429 Retry-After) in seconds; the
        #: circuit breaker uses it as the open-state cooldown
        self.retry_after = retry_after


class _ResumeState:
    """Accumulator for resume-from-prefix stream replay.

    While a streaming response relays, every SSE event is parsed on the
    side to accumulate the generated text. If the backend dies
    mid-stream, the failover loop re-dispatches to a surviving backend
    with that text appended to the prompt (continuation semantics) and
    splices the continuation into the SAME prepared client response —
    events are rewritten to the original stream id/created and the final
    usage chunk is adjusted, so the client sees one seamless completion.
    Under greedy (temperature-0) sampling the spliced text is
    bit-identical to an uninterrupted run; under sampling the suffix is
    a fresh draw from the same prefix (docs/resilience.md)."""

    def __init__(self, chat: bool):
        self.chat = chat
        #: the prepared client StreamResponse (set after first prepare);
        #: its existence is what makes a plain failover impossible
        self.resp: Optional[web.StreamResponse] = None
        self.stream_id: Optional[str] = None
        self.created: Optional[int] = None
        self.text = ""          # generated text relayed so far
        self.chunks = 0         # content-bearing events relayed so far
        self.offset = 0         # chunks relayed before the CURRENT attempt
        self.finished = False   # finish_reason or [DONE] seen
        self.resumed = 0        # continuation attempts started
        #: completion tokens relayed by FINISHED attempts (token-exact)
        self.tokens_base = 0
        #: cumulative completion_tokens reported by the current attempt's
        #: per-chunk usage (continuous_usage_stats), None until seen
        self.attempt_tokens: Optional[int] = None
        #: ORIGINAL prompt token count, when known up front (disaggregated
        #: prefill learns it from the prefill hop's usage). A continuation
        #: backend reports the prompt + relayed prefix as prompt_tokens;
        #: with this set, rewrite() restores the client-visible count so
        #: usage is token-exact against an uninterrupted unified run.
        self.prompt_tokens: Optional[int] = None

    def completion_tokens(self) -> int:
        """Completion tokens relayed so far. One SSE event can carry
        several tokens (fused engine steps, stop-string holdback flush),
        so the per-chunk usage the router requests via
        continuous_usage_stats is authoritative; the content-event count
        is the floor for backends that ignore the flag."""
        attempt = self.chunks - self.offset
        if self.attempt_tokens is not None:
            attempt = max(self.attempt_tokens, attempt)
        return self.tokens_base + attempt

    def start_attempt(self) -> None:
        """Snapshot the accounting before a continuation attempt: what
        was relayed so far becomes the fixed prefix the new backend is
        asked to continue from."""
        self.tokens_base = self.completion_tokens()
        self.offset = self.chunks
        self.attempt_tokens = None

    def observe(self, event: bytes) -> None:
        """Fold one raw SSE event into the accumulated state."""
        ev = event.strip()
        if not ev.startswith(b"data: "):
            return
        if ev == b"data: [DONE]":
            self.finished = True
            return
        try:
            data = json.loads(ev[6:])
        except Exception:
            return
        if self.stream_id is None and data.get("id"):
            self.stream_id = data.get("id")
            self.created = data.get("created")
        usage = data.get("usage")
        if isinstance(usage, dict) \
                and isinstance(usage.get("completion_tokens"), int):
            self.attempt_tokens = usage["completion_tokens"]
        for c in data.get("choices") or []:
            piece = ((c.get("delta") or {}).get("content") if self.chat
                     else c.get("text"))
            if piece:
                self.text += piece
                self.chunks += 1
            if c.get("finish_reason"):
                self.finished = True

    def rewrite(self, event: bytes) -> bytes:
        """Make a continuation event look like part of the original
        stream: original id/created, usage adjusted to cover the whole
        completion (completion_tokens += tokens relayed by the dead
        attempts; the continuation reports only its own)."""
        ev = event.strip()
        if not ev.startswith(b"data: ") or ev == b"data: [DONE]":
            return event
        try:
            data = json.loads(ev[6:])
        except Exception:
            return event
        if self.stream_id is not None:
            data["id"] = self.stream_id
        if self.created is not None:
            data["created"] = self.created
        usage = data.get("usage")
        if isinstance(usage, dict) and (self.tokens_base
                                        or self.prompt_tokens is not None):
            if self.prompt_tokens is not None:
                usage["prompt_tokens"] = self.prompt_tokens
            usage["completion_tokens"] = (
                (usage.get("completion_tokens") or 0) + self.tokens_base)
            usage["total_tokens"] = (
                (usage.get("prompt_tokens") or 0)
                + usage["completion_tokens"])
        return b"data: " + json.dumps(data).encode()


class StreamInterrupted(Exception):
    """A streaming backend died AFTER the client response was prepared.
    Too late for a clean failover (headers and bytes are out), but not
    too late to resume: carries the :class:`_ResumeState` so the
    failover loop can replay the remainder from the generated prefix."""

    def __init__(self, state: _ResumeState, msg: str):
        super().__init__(msg)
        self.state = state


def _continuation_body(body: dict, state: _ResumeState) -> dict:
    """The re-dispatch request: original request with the generated
    prefix appended (completions: onto the prompt; chat: as a trailing
    assistant message with continue_final_message) and the token budget
    reduced by what was already streamed. A greedy engine picks up
    exactly where the dead one stopped."""
    out = dict(body)
    if state.chat:
        msgs = list(body.get("messages") or [])
        msgs.append({"role": "assistant", "content": state.text})
        out["messages"] = msgs
        out["continue_final_message"] = True
        out["add_generation_prompt"] = False
    else:
        out["prompt"] = (body.get("prompt") or "") + state.text
    for key in ("max_tokens", "max_completion_tokens"):
        if isinstance(body.get(key), int):
            out[key] = max(1, body[key] - state.completion_tokens())
    return out


def _synth_first_events(pre_data: dict, chat: bool) -> list[bytes]:
    """SSE events recreating what a streaming engine would have sent for
    the prefill hop's single token: the role-delta opener plus a content
    delta (chat), or one text chunk (completions). Stamped with the
    prefill response's id/created — the resume accumulator adopts that
    id and rewrites every decode-hop event to it, so the client sees one
    coherent stream."""
    rid = pre_data.get("id")
    created = pre_data.get("created")
    model = pre_data.get("model")
    choice = (pre_data.get("choices") or [{}])[0]
    base = {"id": rid,
            "object": "chat.completion.chunk" if chat else "text_completion",
            "created": created, "model": model}
    if chat:
        text = (choice.get("message") or {}).get("content") or ""
        events = [
            {**base, "choices": [{"index": 0, "delta": {"role": "assistant"},
                                  "finish_reason": None}]},
            {**base, "choices": [{"index": 0, "delta": {"content": text},
                                  "finish_reason": None}]},
        ]
    else:
        events = [
            {**base, "choices": [{"index": 0, "text": choice.get("text") or "",
                                  "logprobs": None, "finish_reason": None}]},
        ]
    return [b"data: " + json.dumps(e).encode() for e in events]


def _overload_retry_after(backend) -> Optional[float]:
    """Seconds from a 429's Retry-After header, or None when the 429
    should be relayed to the client verbatim (no/malformed header)."""
    if backend.status != 429:
        return None
    ra = backend.headers.get("Retry-After")
    if ra is None:
        return None
    try:
        return max(0.0, float(ra))
    except ValueError:
        return None


def _split_sse_event(buf: bytes):
    """Split off the first complete SSE event. SSE allows LF or CRLF line
    endings, so the event delimiter is the earliest of \\n\\n / \\r\\n\\r\\n.
    Returns (event, delimiter, rest) or (buf, None, b"")."""
    i_lf = buf.find(b"\n\n")
    i_crlf = buf.find(b"\r\n\r\n")
    if i_crlf >= 0 and (i_lf < 0 or i_crlf < i_lf):
        return buf[:i_crlf], b"\r\n\r\n", buf[i_crlf + 4:]
    if i_lf >= 0:
        return buf[:i_lf], b"\n\n", buf[i_lf + 2:]
    return buf, None, b""


def _strip_inline_usage(event: bytes) -> bytes:
    """Remove the router-injected continuous_usage_stats field from a
    content-bearing chunk before relay — the client asked for a plain
    OpenAI stream. Final chunks (finish_reason set, or the usage-only
    include_usage chunk) pass through untouched so client-requested
    usage reporting still works."""
    if b'"usage"' not in event:  # cheap pre-filter: keep the per-token
        return event             # delta hot path byte-preserving
    ev = event.strip()
    if not ev.startswith(b"data: ") or ev == b"data: [DONE]":
        return event
    try:
        data = json.loads(ev[6:])
    except Exception:
        return event
    choices = data.get("choices")
    if not choices or "usage" not in data:
        return event
    if any(c.get("finish_reason") for c in choices):
        return event
    del data["usage"]
    return b"data: " + json.dumps(data).encode()


def _is_role_only_event(event: bytes) -> bool:
    """True for a chat chunk whose every choice is a bare role delta (no
    content, no finish_reason) — the stream-opening chunk. A continuation
    backend emits its own; relaying it would hand the client a second
    'assistant' role marker mid-stream."""
    if b'"role"' not in event:
        return False
    ev = event.strip()
    if not ev.startswith(b"data: ") or ev == b"data: [DONE]":
        return False
    try:
        data = json.loads(ev[6:])
    except Exception:
        return False
    choices = data.get("choices")
    if not choices or data.get("usage"):
        return False
    for c in choices:
        delta = c.get("delta")
        if not isinstance(delta, dict) or "role" not in delta:
            return False
        if delta.get("content") or c.get("finish_reason"):
            return False
    return True


def _is_usage_only_event(event: bytes) -> bool:
    """True for the OpenAI include_usage final chunk: empty choices + usage."""
    if b'"usage"' not in event:  # cheap pre-filter: skip JSON parse on the
        return False             # per-token delta hot path
    event = event.strip()
    if not event.startswith(b"data: ") or event == b"data: [DONE]":
        return False
    try:
        data = json.loads(event[6:])
    except Exception:
        return False
    return isinstance(data, dict) and data.get("choices") == [] \
        and data.get("usage") is not None


def _extract_usage(tail: bytes, stream: bool) -> Optional[dict]:
    """Pull the usage object from a JSON body or the last SSE data chunks."""
    try:
        if not stream:
            return json.loads(tail).get("usage")
        for line in reversed(tail.split(b"\n")):
            line = line.strip()
            if line.startswith(b"data: ") and line != b"data: [DONE]":
                data = json.loads(line[6:])
                if data.get("usage"):
                    return data["usage"]
        return None
    except Exception:
        return None
