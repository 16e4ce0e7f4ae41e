"""Engine-side tracing. Three things live here: the OpenTelemetry request
span (below), the step clock (`StepClock`), which says where the engine
thread's time goes, as counters and as profiler annotations, and the start
clock (`StartClock`), which says where a replica's start went, on the same
clock.

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

import contextlib
import logging
import os
import random
import time
from collections import deque
from typing import Optional

import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from production_stack_tpu.ops.kda_pallas import CHUNK

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
# `postprocess` for what step() returns. `observe` is the worker's note of
# the step it has just ended (the step histogram's observer), `other` the
# time with no phase open: between two steps driven by hand
HOST_PHASES = ("intake", "schedule", "build", "snapshot", "commit", "launch",
               "postprocess", "deliver", "prefetch_wait", "observe", "other")
STEP_KINDS = ("decode", "ragged", "other")
DISPATCH_KINDS = STEP_KINDS[:-1]  # `other` dispatched nothing
# a step is slow when it took more than SLOW_FACTOR times the median of
# the SLOW_WINDOW steps like it before it (of its kind and stream width,
# after a step of the same kind and width as the one before it: a step
# waits out the dispatch before it, so a decode step after a ragged step is
# held against its own like, and a budget-wide ragged step among narrow
# ones is no stall); the last SLOW_RING are kept
SLOW_WINDOW, SLOW_FACTOR, SLOW_RING = 32, 2.0, 32
_SWITCHES = 16  # phase switches kept for `phase_at`: more than a step makes


class StepClock:
    """Owned by the engine thread (no lock: `stats()` and the event
    loop's heartbeat on other threads read floats and tuples). A step's
    kind is known only after scheduling, so the open step's phases collect
    in a scratch table and are charged to the kind when the step ends.
    From the first switch on some phase is always open (`other` when the
    caller named none), so host + wait + idle is the thread's wall time."""

    def __init__(self):
        self.step_num = 0        # engine_step annotations opened so far
        self.in_step = False
        self.kind = "other"      # of the open step; `describe` sets it
        # of the last step ended ("idle" once the thread has waited for
        # work since), and the kind of dispatch that step waited for first
        # ("none": it only launched): what a request taken in now stood
        # behind. A ragged dispatch is launched by a `ragged` step and
        # waited for by the step after it, whatever that one's kind
        self.last_kind = self.last_wait = "idle"
        # the stream width of the last step's dispatch (0: not a ragged
        # step), for `_note_step`
        self.last_width = 0
        self._waited: Optional[str] = None  # by the open step, first
        self.launch_t = 0.0      # stamp of the last `launch`
        self.compiles = 0        # programs built (`note_build`)
        # kind -> phase -> [wall seconds, on-CPU seconds]
        self.seconds = {k: {p: [0.0, 0.0] for p in (*HOST_PHASES, "wait")}
                        for k in STEP_KINDS}
        self.steps = dict.fromkeys(STEP_KINDS, 0)
        self.idle_seconds = 0.0
        # the first switch and the last flush: between them the seconds
        # above add up to flushed_at - started_at, nothing dropped
        self.started_at = self.flushed_at = 0.0
        # kind -> cause -> seconds of the steps found slow; every kind is
        # there from the start, so the family is exported at 0
        self.slow_seconds = {k: {"wait": 0.0} for k in DISPATCH_KINDS}
        self.slow_steps: deque = deque(maxlen=SLOW_RING)
        # (kind and width of the step before, kind, width) -> the last
        # SLOW_WINDOW such steps' (seconds, {phase: seconds})
        self._recent: dict = {}
        self._scratch: dict = {}  # phase -> [wall, cpu] since the last flush
        self._at_begin: dict = {}  # phase -> wall in _scratch at begin_step
        self._phase: Optional[str] = None  # None: not started
        self._t = self._cpu = self._t_begin = 0.0
        self._compiles_at_begin = 0
        self._builds: list = []  # programs built inside the open step
        self._ann = self._step_ann = None
        self._launch: dict = {}
        # (stamp, phase) of the last switches, newest at _n - 1
        self._switches = [(0.0, "none")] * _SWITCHES
        self._n = 0

    @staticmethod
    def now() -> float:
        """The clock of every stamp the engine takes of a request, so
        that they subtract from the phase switches and from each other."""
        return time.monotonic()

    def enter(self, phase: str, **attrs) -> float:
        """End the open phase and start ``phase``; returns the stamp, so a
        caller that needs a duration subtracts two of them. Entering the
        phase that is open (and naming nothing new for its annotation)
        only reads the clock."""
        if phase == self._phase and not attrs:
            return time.monotonic()
        ann = TraceAnnotation("step." + phase, **attrs)
        t = self._switch(phase)
        self._hand_over(ann)
        return t

    def _switch(self, phase: str) -> float:
        """Charge the time since the last switch to the phase that was
        open and note the switch for `phase_at`. The caller hands the
        annotation over (`_hand_over`) once its own books are done."""
        t, cpu = time.monotonic(), time.thread_time()
        if self._phase is not None:
            acc = self._scratch.setdefault(self._phase, [0.0, 0.0])
            acc[0] += t - self._t
            acc[1] += cpu - self._cpu
        else:
            self.started_at = t
        self._phase, self._t, self._cpu = phase, t, cpu
        self._switches[self._n % _SWITCHES] = (t, phase)
        self._n += 1
        return t

    def _hand_over(self, ann, *between) -> None:
        """Close the open phase's annotation and open ``ann`` (None: the
        phase `other`, which has none) with nothing in between but
        ``between`` (the step's own annotation, entered or left at a
        step's edge), so that a profile shows no hole at a switch."""
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        for call in between:
            call()
        if ann is not None:
            ann.__enter__()
        self._ann = ann

    def wait(self, kind: str) -> float:
        """Enter `wait` for the results of a dispatch of ``kind``."""
        if self._waited is None:
            self._waited = kind
        return self.enter("wait")

    def phase_at(self, t: float) -> str:
        """The phase that was open at ``t``, for another thread: a host
        phase, ``wait``, ``idle``, or ``none`` where ``t`` lies before the
        switches kept (or the clock never started)."""
        n, ring = self._n, list(self._switches)
        for i in range(n - 1, max(n - _SWITCHES, 0) - 1, -1):
            at, phase = ring[i % _SWITCHES]
            if at <= t:
                return phase
        return "none"

    def idle(self) -> float:
        """The thread is about to block on its intake queue."""
        self.last_kind = self.last_wait = "idle"
        self.last_width = 0
        return self.enter("idle")

    def begin_step(self) -> None:
        """Open a step in its first phase, `schedule`: the step's own
        annotation first, so that the phase nests in it."""
        self.step_num += 1
        self.in_step = True
        self._step_ann = StepTraceAnnotation("engine_step",
                                             step_num=self.step_num)
        ann = TraceAnnotation("step.schedule")
        self._t_begin = self._switch("schedule")
        self._hand_over(ann, self._step_ann.__enter__)
        self._at_begin = {p: acc[0] for p, acc in self._scratch.items()}
        self._compiles_at_begin = self.compiles
        self._builds = []

    def note_build(self, build: dict) -> None:
        """A program was built (`perf_accounting.CompileTracker`: its
        first call, in stages) on this thread: the step it ran in goes
        into the build, and the build into the slow-step ring if the step
        turns out slow (cause `compile`)."""
        self.compiles += 1
        build["engine_step"] = self.step_num if self.in_step else None
        if self.in_step:
            self._builds.append(build)

    def describe(self, kind: str, rows: int, tokens: int,
                 width: int = 0) -> None:
        """What the open step dispatches: its kind for the counters, and
        what `step.launch` carries into the trace. ``width``: the stream
        width a ragged step runs at (`LLMEngine._run_ragged`)."""
        self.kind = kind
        self._launch = {"kind": kind, "rows": rows, "tokens": tokens}
        if width:
            self._launch["width"] = width

    def launch(self, **attrs) -> float:
        """``attrs``: what the runner adds to the annotation (a looped
        stack's ``passes``)."""
        self.launch_t = self.enter("launch", **self._launch, **attrs)
        return self.launch_t

    def end_step(self, then: str = "other") -> float:
        """Charge what collected since the last flush to the step's kind
        (outside a step: to "other") and return the step's seconds,
        begin to end. ``then``: the phase the caller is in from here on
        (the worker: `observe`), opened before the bookkeeping below so
        that it, too, is inside a phase and an annotation."""
        ann = TraceAnnotation("step." + then) if then != "other" else None
        t = self._switch(then)
        if self.in_step:
            self._hand_over(
                ann, lambda: self._step_ann.__exit__(None, None, None))
        else:
            self._hand_over(ann)
        self.flushed_at = t
        by_phase = self.seconds[self.kind]
        for phase, (wall, cpu) in self._scratch.items():
            if phase == "idle":
                self.idle_seconds += wall
            else:
                by_phase[phase][0] += wall
                by_phase[phase][1] += cpu
        if not self.in_step:
            self._scratch.clear()
            return 0.0
        seconds = t - self._t_begin
        if self.kind != "other":
            self._note_step(seconds)
        self._scratch.clear()
        self.steps[self.kind] += 1
        self.in_step = False
        self.last_kind, self.last_wait = self.kind, self._waited or "none"
        self.last_width = self._launch.get("width", 0)
        self.kind, self._launch, self._waited = "other", {}, None
        return seconds

    def _note_step(self, seconds: float) -> None:
        """Hold the step that ends against the steps like it before it
        (a running median over the last SLOW_WINDOW: one stall does not
        move it, a change of regime is taken in after half a window), and
        keep it if it is slow, with the phase that overran most."""
        in_step = {p: acc[0] - self._at_begin.get(p, 0.0)
                   for p, acc in self._scratch.items()}
        recent = self._recent.setdefault(
            (self.last_kind, self.last_width, self.kind,
             self._launch.get("width", 0)),
            deque(maxlen=SLOW_WINDOW))
        if len(recent) == SLOW_WINDOW:
            ref = _median([s for s, _ in recent])
            if seconds > SLOW_FACTOR * ref:
                if self.compiles != self._compiles_at_begin:
                    cause = "compile"
                else:
                    cause = max(in_step, key=lambda p: in_step[p] - _median(
                        [by.get(p, 0.0) for _, by in recent]))
                by_cause = self.slow_seconds[self.kind]
                by_cause[cause] = by_cause.get(cause, 0.0) + seconds
                entry = {
                    "step": self.step_num, **self._launch,
                    "kind": self.kind, "after": self.last_kind,
                    "after_width": self.last_width,
                    "seconds": seconds,
                    "reference": ref, "phases": in_step, "cause": cause}
                if cause == "compile":
                    # what was built, each with its seconds by stage
                    entry["builds"] = [
                        {k: b[k] for k in ("kind", "bucket", "seconds",
                                           "stages", "cache_hit")}
                        for b in self._builds]
                self.slow_steps.append(entry)
        recent.append((seconds, in_step))

    def snapshot(self) -> dict:
        """The `step_phases` block of /debug/perf: seconds by kind and
        phase (wall and on-CPU), steps by kind, idle seconds, and the
        stamps of the first switch and the last flush (StepClock.now's
        clock): all the seconds together are their difference."""
        return {
            "steps": dict(self.steps),
            "idle_seconds": self.idle_seconds,
            "started_at": self.started_at,
            "flushed_at": self.flushed_at,
            "seconds": {k: {p: {"wall": w, "cpu": c}
                            for p, (w, c) in by_phase.items()}
                        for k, by_phase in self.seconds.items()},
        }

    def slow_snapshot(self) -> dict:
        """`slow_steps` of /debug/perf: the seconds of the steps found
        slow by kind and cause (vllm:engine_slow_step_seconds_total), and
        the last of them."""
        return {"seconds": {k: dict(v) for k, v in self.slow_seconds.items()},
                "last": list(self.slow_steps)}


def _median(values: list) -> float:
    values = sorted(values)
    mid = len(values) // 2
    return (values[mid] if len(values) % 2
            else (values[mid - 1] + values[mid]) / 2)


def build_annotation(kind: str, bucket: str) -> TraceAnnotation:
    """The profiler annotation of a program's build (`perf_accounting.
    CompileTracker`), to be entered around the first call: it opens inside
    `step.launch`, so a program built in a profiled window names its own
    idle gap on the device trace's clock; a no-op without a session. The
    tracker adds `cache_hit` (`set_metadata`) before it leaves."""
    return TraceAnnotation("build", kind=kind, bucket=bucket)


# -- a replica's start, in parts ------------------------------------------------
# Where the time from the process's creation to `/ready` goes, always on:
# some twenty stamps in a process's life, on the clock of `StepClock` and of
# a request's `timeline`, kept as spans and read as plain floats at scrape
# time (vllm:engine_start_seconds{phase}). The phases, top level first, then
# `engine_build`'s children in the order they run; `engine_build.self` is
# the parent's time outside its children (mesh, model lookup, scheduler,
# the step programs' `jax.jit` wrappers: nothing compiles there).
START_TOP = ("process", "backend_open", "engine_build", "server_bind",
             "warmup")
START_PHASES = START_TOP + (
    "tokenizer", "weights.make", "weights.quantize", "weights.lay_out",
    "kv_pool", "device_drain", "engine_build.self")


def _process_created(now: float) -> float:
    """The stamp, on `time.monotonic()`'s clock, of this process's
    creation: its start time in `/proc/self/stat` (field 22, clock ticks
    since boot) against `CLOCK_BOOTTIME`. ``now`` where that cannot be
    read or reads as the future."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return now
    return now - age if age > 0.0 else now


class StartClock:
    """Spans of (name, start, end, parent) on `StepClock.now()`'s clock.
    Created at `main()`'s entry (by `LLMEngine` where no `main()` ran: a
    test's engine), which closes the first span, `process`: the
    interpreter and the imports. A span opened while another is open is
    its child; children of one parent follow each other, so a parent's
    self time (its own minus its children's) is what ran between them and
    is never negative. Spans open and close on whichever thread runs the
    start (the main thread, then the event loop's), one after the other:
    no lock. A span that covers asynchronous dispatches ends at the host's
    return; `device_drain` is the one wait for the device."""

    def __init__(self):
        entry = time.monotonic()
        self.created = _process_created(entry)
        self.spans: list = []    # [name, start, end (None: open), parent]
        self._open: list = []    # indices into `spans`, innermost last
        self.ready_at: Optional[float] = None
        self.end(self.begin("process", at=self.created), at=entry)

    def begin(self, name: str, at: Optional[float] = None) -> int:
        parent = self.spans[self._open[-1]][0] if self._open else None
        self.spans.append(
            [name, time.monotonic() if at is None else at, None, parent])
        self._open.append(len(self.spans) - 1)
        return self._open[-1]

    def end(self, index: int, at: Optional[float] = None) -> float:
        """Close the span ``index`` (and any left open inside it, as
        after an exception); returns its seconds."""
        at = time.monotonic() if at is None else at
        while self._open:
            i = self._open.pop()
            self.spans[i][2] = at
            if i == index:
                break
        span = self.spans[index]
        return span[2] - span[1]

    @contextlib.contextmanager
    def span(self, name: str):
        index = self.begin(name)
        try:
            yield
        finally:
            self.end(index)

    def mark_ready(self) -> None:
        """The instant `/ready` first would answer 200; the first call
        holds."""
        if self.ready_at is None:
            self.ready_at = time.monotonic()

    def open_since(self, name: str) -> Optional[float]:
        """Seconds the open span ``name`` has run, None if none is open."""
        for i in self._open:
            if self.spans[i][0] == name:
                return time.monotonic() - self.spans[i][1]
        return None

    def seconds(self) -> dict:
        """{phase: seconds} for every name of `START_PHASES`: the closed
        spans begun before `ready` (weights made again at a wake are in
        `spans` and not here: the numbers are fixed once ready); a phase
        that never ran reads 0.0."""
        out = dict.fromkeys(START_PHASES, 0.0)
        inside: dict = {}
        for name, start, end, parent in self.spans:
            if end is None or (self.ready_at is not None
                               and start > self.ready_at):
                continue
            out[name] = out.get(name, 0.0) + end - start
            if parent is not None:
                inside[parent] = inside.get(parent, 0.0) + end - start
        built = out["engine_build"]  # 0.0 while it is open
        out["engine_build.self"] = (
            built - inside.get("engine_build", 0.0) if built else 0.0)
        return out

    @property
    def to_ready(self) -> float:
        """Creation to `ready`, 0.0 until then."""
        return (0.0 if self.ready_at is None
                else self.ready_at - self.created)

    def snapshot(self) -> dict:
        """`start` of /debug/perf: the spans as recorded (stamps on
        `StepClock.now()`'s clock), seconds by phase, creation to ready,
        and what of that no top-level span covers (`main()` between its
        entry and the backend's opening: arguments, configuration)."""
        seconds = self.seconds()
        return {
            "created": self.created, "ready_at": self.ready_at,
            "to_ready_seconds": self.to_ready,
            "seconds": seconds,
            "outside_spans_seconds": (
                self.to_ready - sum(seconds[p] for p in START_TOP)
                if self.ready_at is not None else None),
            "spans": [{"name": n, "start": s, "end": e, "parent": p}
                      for n, s, e, p in self.spans]}


# -- the event loop's heartbeat ------------------------------------------------

class LoopLag:
    """How late the server's event loop runs, and what the engine thread
    was doing meanwhile. One timer on the loop, due ``PERIOD`` seconds
    (half to one and a half of it, drawn anew each time) after its last
    wake (no catch-up): at each wake the lag is now minus due, charged to
    the step clock's phase at the DUE instant. The draw keeps due instants
    uniform against a step period of any length (a fixed period re-armed
    from a late wake would fall on a lattice behind each step's end), so
    lag over ticks
    is the mean time until the loop next runs: what a request that
    arrives on its own pays before its handler stamps ``received``. An
    idle loop reads up to a millisecond (epoll's timeout is whole
    milliseconds), a mean of half of one. Lag alone does not say whether
    the loop was kept from running or had work of its own before the
    beat, so each wake also reads the loop thread's CPU clock: on-CPU
    seconds over wall seconds is how busy the loop itself is. Plain
    floats, read at scrape time; owned by the event loop's thread."""

    # ten beats a second: at a hundred the beats cost OLMoE's cell 0.6 %
    # of its rate on the chip (PERF.md section 6, PR 41)
    PERIOD = 0.100

    def __init__(self, clock: StepClock):
        self.clock = clock
        self.ticks = 0
        self.lag_seconds: dict = {"wait": 0.0}  # phase at due -> seconds
        self.max_lag = 0.0   # since `take_max`
        # the loop thread's wall and on-CPU seconds, start to last wake
        self.wall_seconds = self.cpu_seconds = 0.0
        self._due = self._t0 = self._cpu0 = 0.0
        self._loop = self._handle = None

    def start(self, loop) -> None:
        """On the loop's own thread (its CPU clock is read here)."""
        self._loop = loop
        self._t0, self._cpu0 = self.clock.now(), time.thread_time()
        self._arm()

    def stop(self) -> None:
        if self._handle is not None:
            self._handle.cancel()
        self._loop = self._handle = None

    def _arm(self) -> None:
        delay = self.PERIOD * random.uniform(0.5, 1.5)
        self._due = self.clock.now() + delay
        self._handle = self._loop.call_later(delay, self._wake)

    def _wake(self) -> None:
        now = self.clock.now()
        lag = max(0.0, now - self._due)
        self.wall_seconds = now - self._t0
        self.cpu_seconds = time.thread_time() - self._cpu0
        during = self.clock.phase_at(self._due)
        self.lag_seconds[during] = self.lag_seconds.get(during, 0.0) + lag
        self.ticks += 1
        self.max_lag = max(self.max_lag, lag)
        self._arm()

    def take_max(self) -> float:
        """The largest lag since the last call (the scrape's)."""
        largest, self.max_lag = self.max_lag, 0.0
        return largest

    def snapshot(self) -> dict:
        """`loop_lag` of /debug/perf."""
        return {"period_seconds": self.PERIOD, "ticks": self.ticks,
                "lag_seconds": dict(self.lag_seconds),
                "max_lag_seconds": self.max_lag,
                "wall_seconds": self.wall_seconds,
                "cpu_seconds": self.cpu_seconds}


# -- a request's time to first token, in parts --------------------------------
# The stamps of a flight record's timeline, in the order a request takes
# them, all on StepClock.now(); a part is the time between two neighbours,
# so the parts of a request that has them all add up to first_chunk_written
# - received exactly. `received`, `enqueued` and `first_chunk_written` are
# taken on the event loop, the rest on the engine thread.
TTFT_STAMPS = ("received", "enqueued", "arrival", "admitted", "first_launch",
               "first_token", "first_chunk_written")
TTFT_PARTS = ("server_prep", "intake_wait", "queue_wait", "stream_wait",
              "prefill_steps", "server_deliver")
# what the span's two older stages are made of: before admission, and
# from there to the first token
QUEUE_PARTS, PREFILL_PARTS = TTFT_PARTS[:3], TTFT_PARTS[3:5]


def ttft_parts(timeline: dict) -> dict:
    """{part: seconds} for every part whose two stamps ``timeline`` holds
    (a response that is not streamed has no ``first_chunk_written``)."""
    return {part: timeline[b] - timeline[a]
            for part, a, b in zip(TTFT_PARTS, TTFT_STAMPS, TTFT_STAMPS[1:])
            if a in timeline and b in timeline}


class TtftParts:
    """Seconds and requests by part since start: `ttft_parts` of
    /debug/perf and vllm:request_ttft_part_seconds_total{part} beside
    vllm:request_ttft_parts_total{part}. Owned by the event loop."""

    def __init__(self):
        self.seconds = dict.fromkeys(TTFT_PARTS, 0.0)
        self.count = dict.fromkeys(TTFT_PARTS, 0)

    def add(self, parts: dict) -> None:
        for part, s in parts.items():
            self.seconds[part] += s
            self.count[part] += 1

    def snapshot(self) -> dict:
        return {part: {"seconds": self.seconds[part],
                       "count": self.count[part]} for part in TTFT_PARTS}


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
    those alone. The scan takes a span a block of ``kda_pallas.CHUNK``
    rows at a time, the last one masked: ``chunk_block_rows`` counts the
    rows of the blocks it runs, padding included."""

    def __init__(self, kda_layers: int, state_bytes: int,
                 kind: str = "kda"):
        """``kind``: "kda"; "mamba" for a stack of state-space layers,
        whose span kernel takes rows one by one (no blocks to count); "ssd"
        for a stack whose every layer holds a state-space mixer with heads
        and "gdn" for Gated DeltaNet layers (blocks of their own size, not
        counted). The counters carry the name."""
        self.kda_layers, self.state_bytes = kda_layers, state_bytes
        self.kind = kind
        self.decode_calls = 0   # decode dispatches x iterations x layers
        self.chunk_tokens = 0   # rows the span scan carried, a layer
        self.chunk_spans = 0    # spans it carried (state loaded, stored)
        self.chunk_block_rows = 0  # rows of the blocks it ran, a layer
        self.state_resets = 0   # sequences started from a zero state

    def record_decode(self, iterations: int) -> None:
        self.decode_calls += iterations * self.kda_layers

    def record_ragged(self, q_len, decode_rows, resets: int) -> None:
        """``q_len`` (slots,) rows of each slot's span in one ragged
        dispatch, ``decode_rows`` (slots,) bool: the spans the decode
        step takes."""
        scanned = np.where(decode_rows, 0, q_len)
        self.chunk_tokens += int(scanned.sum())
        self.chunk_block_rows += int((-(-scanned // CHUNK) * CHUNK).sum())
        self.chunk_spans += int((scanned > 0).sum())
        self.state_resets += resets

    def snapshot(self, lookups_bypassed: int) -> dict:
        """``lookups_bypassed``: prefix lookups answered "miss", which the
        block allocator counts."""
        blocks = ({"kda_chunk_block_rows_total": self.chunk_block_rows}
                  if self.kind == "kda" else {})
        return {f"{self.kind}_decode_calls_total": self.decode_calls,
                f"{self.kind}_chunk_tokens_total": self.chunk_tokens,
                f"{self.kind}_chunk_spans_total": self.chunk_spans,
                **blocks,
                "recurrent_state_resets_total": self.state_resets,
                "recurrent_state_bytes": self.state_bytes,
                "prefix_lookups_bypassed_total": lookups_bypassed}


# -- a window that binds ------------------------------------------------------

class WindowCounters:
    """What the window layers' attention calls stream beside what they
    would stream without a window, and the cross-attention layers' calls
    (which read another layer's cache rows), always on: plain numbers the
    engine thread adds up where it builds a dispatch, from the span offsets
    and context lengths it already holds. In tokens of context, each once
    a call: a decode step's call streams a live slot's blocks from the one
    that holds its floor (``ctx - window``) to its last, a ragged step's
    walks the context windows ``count_windows`` counts, with the floor and
    without. Both already times the window layers, and both kept by
    program (``PROGRAMS``) beside their totals; the calls of the layers
    that see every row (the full layer; a cross-attention layer reads its
    rows) the same way, ``full_read``. Beside what the calls stream as
    they are built, what any implementation of them has to do at least, by
    program and both kinds of layer together: ``rows_needed`` (a window
    layer's call the rows from its first query's floor to its reach, a
    full layer's its reach, exact, not rounded to blocks) and
    ``pairs_needed`` ((query, key) pairs inside the window or the causal
    reach)."""

    PROGRAMS = ("ragged", "decode")

    def __init__(self, cfg, block_size: int):
        from production_stack_tpu.ops.ragged_paged_attention_pallas import (
            WINDOWS,
        )

        self.window, self.bs = cfg.sliding_window, block_size
        self.kv_bytes_per_token = cfg.kv_bytes_per_token
        self.window_layers = cfg.count_layers("swa")
        self.cross_layers = cfg.count_layers("cross")
        self.full_layers = cfg.count_layers("full") + self.cross_layers
        self.win_tokens = WINDOWS * block_size
        self.context = dict.fromkeys(self.PROGRAMS, 0)  # if no window bound
        self.read = dict.fromkeys(self.PROGRAMS, 0)     # streamed
        # streamed by the calls that see all rows
        self.full_read = dict.fromkeys(self.PROGRAMS, 0)
        self.rows_needed = dict.fromkeys(self.PROGRAMS, 0)
        self.pairs_needed = dict.fromkeys(self.PROGRAMS, 0)
        self.shared_kv_calls = 0

    @property
    def context_tokens(self) -> int:
        return sum(self.context.values())

    @property
    def read_tokens(self) -> int:
        return sum(self.read.values())

    def _spans_needed(self, q_len, context_lens) -> None:
        """A ragged dispatch's spans of ``q_len`` rows that end contexts of
        ``context_lens``: with S(n) = sum over v = 1..n of min(v, window),
        a window layer scores S(ctx) - S(ctx - q) pairs of a span and reads
        the rows from its first query's floor, a full layer the same with
        no window."""
        q = np.asarray(q_len, np.int64)
        ctx = np.where(q > 0, np.asarray(context_lens, np.int64), 0)
        w = self.window

        def tri(n):
            return n * (n + 1) // 2

        def s(n):
            return np.where(n <= w, tri(n), tri(w) + (n - w) * w)

        pairs = (self.window_layers * (s(ctx) - s(ctx - q))
                 + self.full_layers * (tri(ctx) - tri(ctx - q)))
        rows = (self.window_layers * (ctx - np.maximum(ctx - q + 1 - w, 0))
                + self.full_layers * ctx)
        self.pairs_needed["ragged"] += int(pairs.sum())
        self.rows_needed["ragged"] += int(rows[q > 0].sum())

    def record_decode(self, context_lens, iterations: int) -> None:
        """``context_lens`` (slots,): the live contexts a dispatch's first
        iteration attends over (0: an idle slot); the few rows the later
        iterations add are not counted."""
        ctx = np.asarray(context_lens, np.int64)
        end = -(-ctx // self.bs) * self.bs
        floor = np.maximum(ctx - self.window, 0) // self.bs * self.bs
        n = iterations * self.window_layers
        self.context["decode"] += n * int(end.sum())
        self.read["decode"] += n * int((end - floor).sum())
        self.full_read["decode"] += (iterations * self.full_layers
                                     * int(end.sum()))
        # a row scores one pair a row it sees: its window's, its context's
        rows = iterations * (
            self.window_layers * int(np.minimum(ctx, self.window).sum())
            + self.full_layers * int(ctx.sum()))
        self.rows_needed["decode"] += rows
        self.pairs_needed["decode"] += rows
        self.shared_kv_calls += iterations * self.cross_layers

    def record_ragged(self, windows: int, windows_read: int, q_len,
                      context_lens) -> None:
        """``windows`` / ``windows_read``: ``count_windows`` of the
        dispatch without and with the window; ``q_len``, ``context_lens``
        (slots,): its spans."""
        n = self.window_layers * self.win_tokens
        self.context["ragged"] += n * windows
        self.read["ragged"] += n * windows_read
        self.full_read["ragged"] += (self.full_layers * self.win_tokens
                                     * windows)
        self._spans_needed(q_len, context_lens)
        self.shared_kv_calls += self.cross_layers

    def snapshot(self, scheduler) -> dict:
        """``scheduler``: the engine's, for the window pool's allocator
        and what it counted of it (blocks given back by live sequences;
        times a sequence found the pool dry, 0 by the pool's size rule)."""
        allocator = scheduler.window_allocator
        return {"window_attn_context_tokens_total": self.context_tokens,
                "window_attn_read_tokens_total": self.read_tokens,
                "window_attn_by_program": {
                    p: {"context_tokens": self.context[p],
                        "read_tokens": self.read[p],
                        "full_read_tokens": self.full_read[p],
                        "rows_needed": self.rows_needed[p],
                        "pairs_needed": self.pairs_needed[p]}
                    for p in self.PROGRAMS},
                "shared_kv_attn_calls_total": self.shared_kv_calls,
                "kv_bytes_per_token": self.kv_bytes_per_token,
                "window_kv_blocks_total": allocator.num_blocks,
                "window_kv_blocks_free": allocator.num_free_blocks,
                "window_kv_blocks_released_total":
                    scheduler.window_blocks_released,
                "window_kv_block_waits_total": scheduler.window_block_waits}


# -- latent attention ---------------------------------------------------------

class LatentCounters:
    """What the latent attention kernel (``ops/latent_paged_attention_
    pallas.py``) was asked to score, always on: plain numbers the engine
    thread adds up where it builds a dispatch, from the span offsets and
    context lengths it already holds; no device result. Both the ragged
    and the decode step programs call the one kernel once a cache layer,
    so the numbers are by step ``kind`` and already times the layers.

    A span of ``q`` tokens ending a context of ``ctx`` scores, causally,
    ``q * (ctx - q) + q * (q + 1) / 2`` (query, context row) pairs: its
    past whole, its own triangle.

    ``expand_rows``: the kernel's crossover (``EXPAND_ROWS``) where the
    runner's two programs are the kernel's, else None (the XLA form). A
    span of that many tokens or more in a ragged dispatch is scored in the
    published, expanded form, and its pairs are counted a second time as
    such; a decode dispatch's one-token spans never are: they are the
    kernel's decode body's (several sequences a grid cell, long windows),
    and counted a second time as that."""

    KINDS = ("ragged", "decode")

    def __init__(self, cache_layers: int, kv_bytes_per_token: int,
                 expand_rows: Optional[int] = None):
        self.cache_layers = cache_layers
        self.kv_bytes_per_token = kv_bytes_per_token
        self.expand_rows = expand_rows
        self.query_tokens = dict.fromkeys(self.KINDS, 0)
        self.scored_pairs = dict.fromkeys(self.KINDS, 0)
        self.expanded_pairs = dict.fromkeys(self.KINDS, 0)
        self.decode_body_pairs = dict.fromkeys(self.KINDS, 0)
        # context rows the spans reach (each once a span and layer): what
        # a kernel has to read of the pool at least
        self.context_rows = dict.fromkeys(self.KINDS, 0)

    def record(self, kind: str, q_len, context_lens, iterations: int = 1
               ) -> None:
        """``q_len`` (slots,) tokens of each slot's span in one dispatch
        of ``kind``, ``context_lens`` (slots,) their contexts, span
        included; a decode dispatch's ``iterations`` fused steps each
        lengthen every live context by one."""
        q = np.asarray(q_len, np.int64)
        ctx = np.where(q > 0, np.asarray(context_lens, np.int64), 0)
        K, L = iterations, self.cache_layers
        # iteration i finds every live context i rows longer: i rows and,
        # for a one-token span, i pairs more a slot
        longer = int((q > 0).sum()) * (K * (K - 1) // 2)
        pairs = q * (ctx - q) + q * (q + 1) // 2
        scored = (K * int(pairs.sum()) + longer) * L
        self.query_tokens[kind] += K * int(q.sum()) * L
        self.scored_pairs[kind] += scored
        self.context_rows[kind] += (K * int(ctx.sum()) + longer) * L
        if self.expand_rows is None:
            return
        if kind == "ragged":
            self.expanded_pairs[kind] += int(
                pairs[q >= self.expand_rows].sum()) * L
        else:
            self.decode_body_pairs[kind] += scored

    def snapshot(self) -> dict:
        return {"mla_query_tokens_total": dict(self.query_tokens),
                "mla_scored_pairs_total": dict(self.scored_pairs),
                "mla_expanded_pairs_total": dict(self.expanded_pairs),
                "mla_decode_body_pairs_total": dict(self.decode_body_pairs),
                "mla_context_rows_total": dict(self.context_rows),
                "kv_bytes_per_token": self.kv_bytes_per_token}


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
    and loads, their mean and the experts touched are of the held.

    ``grouped_kernel``: the runner built its step programs with the Pallas
    grouped matmul (``ops/moe_grouped_matmul_pallas.py``) and not with
    ``jax.lax.ragged_dot``: its one choice for every program it builds,
    so a layer-step recorded here ran what it says."""

    def __init__(self, num_experts: int, top_k: int, share: bool = False,
                 grouped_kernel: bool = False):
        self.num_experts, self.top_k = num_experts, top_k
        self.share, self.grouped_kernel = share, grouped_kernel
        self.routed_tokens = 0        # (token, choice) pairs sent to experts
        self.held_pairs = 0           # of them, on experts held here
        self.padding_rows = 0         # stream rows kept out of the routing
        self.expert_load_max = 0      # pairs of the busiest expert
        self.decode_experts_touched = 0  # experts with a pair, decode steps
        self.decode_layer_steps = 0   # layers x fused iterations, decode steps
        self.layer_steps = 0          # the same of every kind of dispatch

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
        self.layer_steps += len(loads)
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
            "moe_layer_steps_total": self.layer_steps,
            # of them, on the Pallas kernel: all or none, as built
            "moe_grouped_kernel_layer_steps_total":
                self.layer_steps if self.grouped_kernel else 0,
        }
