"""Engine-side tracing. Two things live here: the OpenTelemetry request span
(below), and the step clock (`StepClock`, at the end), which says where the
engine thread's time goes, as counters and as profiler annotations.

Distributed tracing: the same graceful-degradation layering
as router/experimental/tracing.py, so engine spans JOIN the router's trace
instead of dying at the proxy boundary. The router injects W3C
``traceparent`` into the backend request (request_service._proxy_and_stream);
here we extract it and open a child SERVER span around the engine's
admission → queue → prefill → decode lifecycle.

This image ships only the OpenTelemetry *API*: trace-context propagation
works unconditionally; spans become recording + exported when
opentelemetry-sdk and the OTLP exporter are installed in the deployment
image (init degrades gracefully otherwise).
"""

from __future__ import annotations

import logging
import time
from typing import Optional

import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

logger = logging.getLogger("engine.tracing")

_tracer = None
_propagator = None
_enabled = False


def initialize_tracing(endpoint: Optional[str], service_name: str = "tpu-engine",
                       secure: bool = False) -> bool:
    """Returns True when spans will actually be recorded+exported."""
    global _tracer, _propagator, _enabled
    try:
        from opentelemetry import trace
        from opentelemetry.trace.propagation.tracecontext import (
            TraceContextTextMapPropagator,
        )
    except ImportError:
        # opentelemetry-api not in this image: tracing is a no-op (the
        # engine must boot fine without it)
        if endpoint:
            logger.warning(
                "--otel-endpoint set but opentelemetry-api is not installed; "
                "tracing disabled"
            )
        _enabled = False
        return False

    _propagator = TraceContextTextMapPropagator()
    exporting = False
    if endpoint:
        try:
            from opentelemetry.sdk.resources import Resource
            from opentelemetry.sdk.trace import TracerProvider
            from opentelemetry.sdk.trace.export import BatchSpanProcessor
            from opentelemetry.exporter.otlp.proto.grpc.trace_exporter import (
                OTLPSpanExporter,
            )

            provider = TracerProvider(
                resource=Resource.create({"service.name": service_name})
            )
            provider.add_span_processor(
                BatchSpanProcessor(
                    OTLPSpanExporter(endpoint=endpoint, insecure=not secure)
                )
            )
            trace.set_tracer_provider(provider)
            exporting = True
            logger.info("OTel tracing exporting to %s", endpoint)
        except ImportError:
            logger.warning(
                "--otel-endpoint set but opentelemetry-sdk/exporter not "
                "installed; running with W3C propagation only"
            )
    _tracer = trace.get_tracer("production_stack_tpu.engine")
    _enabled = True
    return exporting


def is_enabled() -> bool:
    return _enabled


def extract_context(headers) -> Optional[object]:
    if not _enabled or _propagator is None:
        return None
    return _propagator.extract(carrier=dict(headers))


def inject_headers(headers: dict, context=None) -> dict:
    if _enabled and _propagator is not None:
        _propagator.inject(carrier=headers, context=context)
    return headers


def trace_id_hex(context=None) -> Optional[str]:
    """32-hex trace id of the current (or given) context, or None when
    tracing is off / there is no active trace — lets the flight recorder
    cross-reference its timeline with the exported trace."""
    if not _enabled:
        return None
    from opentelemetry import trace

    span = trace.get_current_span(context)
    ctx = span.get_span_context()
    if not ctx.trace_id:
        return None
    return format(ctx.trace_id, "032x")


class request_span:
    """SERVER (or CLIENT) span context manager; no-op when tracing is off."""

    def __init__(self, name: str, context=None, kind: str = "server",
                 attributes: Optional[dict] = None):
        self.name = name
        self.context = context
        self.kind = kind
        self.attributes = attributes or {}
        self._cm = None
        self.span = None

    def __enter__(self):
        if not _enabled or _tracer is None:
            return None
        from opentelemetry.trace import SpanKind

        kind = SpanKind.SERVER if self.kind == "server" else SpanKind.CLIENT
        self._cm = _tracer.start_as_current_span(
            self.name, context=self.context, kind=kind,
            attributes=self.attributes,
        )
        self.span = self._cm.__enter__()
        return self.span

    def __exit__(self, *exc):
        if self._cm is not None:
            return self._cm.__exit__(*exc)
        return False


# -- the step clock -----------------------------------------------------------
# Where the engine thread's time goes, always on. One switch per phase
# (`enter`) reads the wall clock and the thread's CPU clock once each, so
# the phases of the worker loop sum to its wall time by construction; the
# same switch opens a `jax.profiler.TraceAnnotation("step.<phase>")`, which
# costs nothing without a profiler session and puts the phase on the device
# trace's clock with one. Counters are plain floats read by
# `LLMEngine.stats()` at scrape time; nothing Prometheus is on the hot path.

# `wait` (blocked on the device) and `idle` (blocked on the intake queue)
# are exported as families of their own, the others as
# vllm:engine_host_seconds_total{kind,phase}. A step's phases come in the
# order listed, except that `deliver` may come twice: mid-step, between
# `launch` and `wait`, when a decode-only step hands over what it resolved
# before it blocks on the decode program (LLMEngine._hand_over), and after
# `postprocess` for what step() returns
HOST_PHASES = ("intake", "schedule", "build", "snapshot", "commit", "launch",
               "postprocess", "deliver", "prefetch_wait")
STEP_KINDS = ("decode", "ragged", "prefill", "other")


class StepClock:
    """Owned by the engine thread (no lock: `stats()` on another thread
    reads floats). A step's kind is known only after scheduling, so the
    open step's phases collect in a scratch table and are charged to the
    kind when the step ends."""

    def __init__(self):
        self.step_num = 0        # engine_step annotations opened so far
        self.in_step = False
        self.kind = "other"      # of the open step; `describe` sets it
        # kind -> phase -> [wall seconds, on-CPU seconds]
        self.seconds = {k: {p: [0.0, 0.0] for p in (*HOST_PHASES, "wait")}
                        for k in STEP_KINDS}
        self.steps = dict.fromkeys(STEP_KINDS, 0)
        self.idle_seconds = 0.0
        self._scratch: dict = {}  # phase -> [wall, cpu] since the last flush
        self._phase: Optional[str] = None
        self._t = self._cpu = self._t_begin = 0.0
        self._ann = self._step_ann = None
        self._launch: dict = {}

    def enter(self, phase: str, **attrs) -> float:
        """End the open phase and start ``phase``; returns the stamp, so a
        caller that needs a duration subtracts two of them."""
        t = self._close()
        self._phase = phase
        self._ann = TraceAnnotation("step." + phase, **attrs)
        self._ann.__enter__()
        return t

    def _close(self) -> float:
        t, cpu = time.monotonic(), time.thread_time()
        if self._phase is not None:
            acc = self._scratch.setdefault(self._phase, [0.0, 0.0])
            acc[0] += t - self._t
            acc[1] += cpu - self._cpu
            self._ann.__exit__(None, None, None)
            self._phase = None
        self._t, self._cpu = t, cpu
        return t

    def begin_step(self) -> None:
        self._t_begin = self._close()
        self.step_num += 1
        self.in_step = True
        self._step_ann = StepTraceAnnotation("engine_step",
                                             step_num=self.step_num)
        self._step_ann.__enter__()

    def describe(self, kind: str, rows: int, tokens: int) -> None:
        """What the open step dispatches: its kind for the counters, and
        what `step.launch` carries into the trace."""
        self.kind = kind
        self._launch = {"kind": kind, "rows": rows, "tokens": tokens}

    def launch(self, **attrs) -> float:
        """``attrs``: what the runner adds to the annotation (a looped
        stack's ``passes``)."""
        return self.enter("launch", **self._launch, **attrs)

    def end_step(self) -> float:
        """Charge what collected since the last flush to the step's kind
        (outside a step: to "other") and return the step's seconds,
        begin to end."""
        t = self._close()
        by_phase = self.seconds[self.kind]
        for phase, (wall, cpu) in self._scratch.items():
            if phase == "idle":
                self.idle_seconds += wall
            else:
                by_phase[phase][0] += wall
                by_phase[phase][1] += cpu
        self._scratch.clear()
        if not self.in_step:
            return 0.0
        self.steps[self.kind] += 1
        self.in_step = False
        self.kind, self._launch = "other", {}
        self._step_ann.__exit__(None, None, None)
        return t - self._t_begin

    def snapshot(self) -> dict:
        """The `step_phases` block of /debug/perf: seconds by kind and
        phase (wall and on-CPU), steps by kind, idle seconds."""
        return {
            "steps": dict(self.steps),
            "idle_seconds": self.idle_seconds,
            "seconds": {k: {p: {"wall": w, "cpu": c}
                            for p, (w, c) in by_phase.items()}
                        for k, by_phase in self.seconds.items()},
        }


# -- looped stacks ------------------------------------------------------------

class LoopCounters:
    """What a looped stack (``ModelConfig.loop_passes`` > 1) ran, always
    on. The step programs of such a model return the number of passes
    their stack made, the outer scan's own carry (``models/llama.py``
    ``forward_hidden``), one int32 per forward; it comes to the host in
    the fetch of the step's own results, like the MoE histogram below.
    ``layer_passes`` over ``layer_steps`` is the passes a layer ran per
    forward: the witness that none was left out."""

    def __init__(self, num_layers: int):
        self.num_layers = num_layers
        self.layer_passes = 0  # layer executions: layers x passes run
        self.layer_steps = 0   # layers x forwards (fused iterations)

    def record(self, passes) -> None:
        """``passes``: integers on the host, one per forward (fused decode
        iteration) of one dispatch."""
        p = np.asarray(passes, np.int64).reshape(-1)
        self.layer_passes += self.num_layers * int(p.sum())
        self.layer_steps += self.num_layers * len(p)

    def snapshot(self) -> dict:
        return {"loop_layer_passes_total": self.layer_passes,
                "loop_layer_steps_total": self.layer_steps}


# -- recurrent-state layers ---------------------------------------------------

class RecurrentCounters:
    """What the recurrent (KDA) layers of a hybrid stack ran, always on:
    plain numbers the engine thread adds up where it builds a dispatch
    (it knows every span it packs), read at scrape time. ``state_bytes``
    is what the state arrays hold, fixed at start-up.

    A ragged stream's spans go two ways (``ops/kda.py``
    ``continues_one_row``): its decode rows, one row that continues a
    stored state, run through the decode step, once a KDA layer a
    dispatch; every other span (a prompt's chunk, a first token) is what
    the span scan carries, and ``chunk_tokens`` / ``chunk_spans`` count
    those alone."""

    def __init__(self, kda_layers: int, state_bytes: int):
        self.kda_layers, self.state_bytes = kda_layers, state_bytes
        self.decode_calls = 0   # decode dispatches x iterations x layers
        self.chunk_tokens = 0   # rows the span scan carried, a layer
        self.chunk_spans = 0    # spans it carried (state loaded, stored)
        self.state_resets = 0   # sequences started from a zero state

    def record_decode(self, iterations: int) -> None:
        self.decode_calls += iterations * self.kda_layers

    def record_ragged(self, q_len, decode_rows, resets: int) -> None:
        """``q_len`` (slots,) rows of each slot's span in one ragged
        dispatch, ``decode_rows`` (slots,) bool: the spans the decode
        step takes."""
        scanned = np.where(decode_rows, 0, q_len)
        self.chunk_tokens += int(scanned.sum())
        self.chunk_spans += int((scanned > 0).sum())
        self.state_resets += resets

    def snapshot(self, lookups_bypassed: int) -> dict:
        """``lookups_bypassed``: prefix lookups answered "miss", which the
        block allocator counts."""
        return {"kda_decode_calls_total": self.decode_calls,
                "kda_chunk_tokens_total": self.chunk_tokens,
                "kda_chunk_spans_total": self.chunk_spans,
                "recurrent_state_resets_total": self.state_resets,
                "recurrent_state_bytes": self.state_bytes,
                "prefix_lookups_bypassed_total": lookups_bypassed}


# -- MoE routing counters -----------------------------------------------------

class MoeCounters:
    """What the MoE block routed, always on, as plain numbers read at
    scrape time. The step programs of an MoE model return one small
    histogram per layer (``models/llama.py`` ``_moe_mlp``: pairs received
    by each expert, then the pairs of the null group that padding rows
    are sent to); it comes to the host in the fetch of the step's own
    results, and ``record`` folds it in: no transfer or device program
    of its own. Everything is summed over layers and dispatches.

    ``num_experts`` is the experts HELD here. Where that is a share of
    the experts routed over (``share``: ``ModelConfig.experts_held``), the
    histogram has one more column before the null group's, the pairs on
    experts that lie elsewhere: they are routed pairs and not held ones,
    and loads, their mean and the experts touched are of the held."""

    def __init__(self, num_experts: int, top_k: int, share: bool = False):
        self.num_experts, self.top_k = num_experts, top_k
        self.share = share
        self.routed_tokens = 0        # (token, choice) pairs sent to experts
        self.held_pairs = 0           # of them, on experts held here
        self.padding_rows = 0         # stream rows kept out of the routing
        self.expert_load_max = 0      # pairs of the busiest expert
        self.decode_experts_touched = 0  # experts with a pair, decode steps
        self.decode_layer_steps = 0   # layers x fused iterations, decode steps

    def record(self, kind: str, hist) -> None:
        """``hist``: (..., X + 1) integers on the host, one row per layer
        (and per fused decode iteration) of one dispatch of ``kind``."""
        h = np.asarray(hist, np.int64).reshape(
            -1, self.num_experts + 1 + self.share)
        loads = h[:, :self.num_experts]
        self.held_pairs += int(loads.sum())
        self.routed_tokens += int(h[:, :-1].sum())
        self.padding_rows += int(h[:, -1].sum()) // self.top_k
        self.expert_load_max += int(loads.max(axis=1).sum())
        if kind == "decode":
            self.decode_experts_touched += int((loads > 0).sum())
            self.decode_layer_steps += len(loads)

    def snapshot(self) -> dict:
        return {
            "moe_routed_tokens_total": self.routed_tokens,
            "moe_held_pairs_total": self.held_pairs,
            "moe_padding_rows_total": self.padding_rows,
            "moe_expert_load_max_total": self.expert_load_max,
            # pairs per expert: what an even routing would give each
            "moe_expert_load_mean_total": (self.held_pairs
                                           / self.num_experts),
            "moe_decode_experts_touched_total": self.decode_experts_touched,
            "moe_decode_layer_steps_total": self.decode_layer_steps,
        }
