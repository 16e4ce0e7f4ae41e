"""Async facade over the synchronous LLMEngine.

One dedicated thread owns the device (JAX dispatch is blocking); asyncio land
talks to it through an intake queue and per-request output queues. This is
the same thread↔event-loop shape the reference router uses for its
background workers (run_coroutine_threadsafe bridges,
reference: src/vllm_router/service_discovery.py:757-765).
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import logging
import queue
import threading
import time
import uuid
from typing import AsyncIterator, Optional, Sequence as Seq

from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.sequence import RequestOutput
from production_stack_tpu.engine.tracing import StepClock


class RequestAborted(Exception):
    """Raised on a request's stream when its sequence was aborted
    (deadline expiry / client disconnect / admin action) while a consumer
    was still reading. Callers that abort their OWN stream cancel the
    consumer first and never see this; it exists so an abort from
    anywhere else can never leave a consumer blocked on q.get()
    forever."""


def _sleep_until(stamp: float) -> None:
    """Block the engine thread until ``stamp`` of the step clock."""
    delay = stamp - StepClock.now()
    if delay > 0:
        time.sleep(delay)


class AsyncEngine:
    def __init__(self, engine: LLMEngine):
        self.engine = engine
        self.intake: queue.Queue = queue.Queue()
        self.streams: dict[str, asyncio.Queue] = {}
        self.loop: Optional[asyncio.AbstractEventLoop] = None
        self.running = False
        self.paused = False  # sleep mode
        self.step_count = 0
        # called with each step's wall duration (seconds) from the engine
        # thread; the server points this at its scheduler-step histogram.
        # Only real steps are timed — the worker blocks on intake when idle.
        self.step_observer = None
        self.thread: Optional[threading.Thread] = None

    async def start(self) -> None:
        self.loop = asyncio.get_running_loop()
        if self.thread is not None and self.thread.is_alive():
            return
        self.running = True
        # asked before a decode program joins the device's queue: what
        # has arrived goes into a ragged step first. With the wait beside
        # it the engine may ask a lead before a landing, and queue the
        # next decode program there (LLMEngine._launch_ahead)
        self.engine.arrival_probe = lambda: not self.intake.empty()
        self.engine.landing_wait = _sleep_until
        self.thread = threading.Thread(target=self._worker, daemon=True)
        self.thread.start()

    def stop(self) -> None:
        self.running = False
        if self.thread is not None:
            self.thread.join(timeout=2.0)
            self.thread = None
        # step() driven by hand again
        self.engine.arrival_probe = self.engine.landing_wait = None

    # -- worker thread -------------------------------------------------------
    def _worker(self) -> None:
        # every moment of this loop belongs to a phase of the engine's
        # step clock (engine/tracing.py): idle, intake and observe here
        # (`observe`: from a step's end to the next intake or, if nothing
        # has arrived, the next step: the step observer's call is in it),
        # the rest inside engine.step(). A decode step returns once its
        # program is launched, with what the landing before it resolved:
        # delivering, observing, the intake and the next step's schedule
        # and build all run while the device does
        clock = self.engine.clock
        while self.running:
            self._drain_intake(block=not self.engine.has_unfinished())
            if self.paused or not self.engine.has_unfinished():
                clock.end_step()  # no step ran: flush idle and intake
                continue
            clock.begin_step()
            try:
                outputs = self.engine.step()
            except Exception as e:
                # a step failure must not kill the worker thread: every
                # open stream would hang forever. Fail the in-flight
                # requests and keep serving.
                logging.getLogger(__name__).exception("engine.step failed")
                err = ValueError(f"engine step failed: {e}")
                if self.loop is not None:
                    for rid in list(self.streams):
                        self.loop.call_soon_threadsafe(
                            self._deliver_error, rid, err
                        )
                for rid in self.engine.live_request_ids():
                    self.engine.abort_request(rid)
                clock.end_step()
                continue
            self.step_count += 1
            if outputs:
                clock.enter("deliver")
                self._hand_over(outputs)
            step_seconds = clock.end_step(then="observe")
            if self.step_observer is not None:
                try:
                    self.step_observer(step_seconds)
                except Exception:
                    logging.getLogger(__name__).debug(
                        "step_observer hook failed", exc_info=True)

    def _drain_intake(self, block: bool) -> None:
        clock = self.engine.clock
        if block:
            clock.idle()
        elif self.intake.empty():
            # nothing arrived during the step: the phase stays `observe`
            # (what comes in from here on is taken after the next step)
            return
        else:
            clock.enter("intake")
        try:
            item = self.intake.get(timeout=0.05 if block else 0)
        except queue.Empty:
            return
        while True:
            # per item: a "call" may run whole steps (warm-up), which end
            # with no phase open
            clock.enter("intake")
            kind, payload = item
            if kind == "add":
                (rid, prompt_ids, sampling, adapter_slot, tenant,
                 enqueued) = payload
                try:
                    self.engine.add_request(
                        rid, prompt_token_ids=prompt_ids, sampling=sampling,
                        adapter_slot=adapter_slot, tenant=tenant,
                        enqueued=enqueued,
                    )
                except Exception as e:  # surfaced on the request's stream
                    if self.loop is not None:
                        self.loop.call_soon_threadsafe(self._deliver_error, rid, e)
            elif kind == "abort":
                aborted = self.engine.abort_request(payload)
                if aborted and self.loop is not None:
                    # wake any consumer still blocked on q.get(): the
                    # aborted sequence will never emit a finished output.
                    # Streams whose consumer initiated the abort (stop
                    # strings, _abort_all) are already deregistered or
                    # cancelled, so this is a no-op for them.
                    self.loop.call_soon_threadsafe(
                        self._deliver_error, payload,
                        RequestAborted(f"request {payload} aborted"),
                    )
            elif kind == "call":
                fn, fut = payload
                try:
                    result = fn(self.engine)
                except Exception as e:
                    err = e
                    result = None
                else:
                    err = None
                # the awaiting task may have been cancelled meanwhile
                # (asyncio.wrap_future propagates cancellation to this
                # future); set_result would then raise InvalidStateError
                # and kill the worker thread — every later stream would
                # hang forever
                try:
                    if not fut.cancelled():
                        if err is not None:
                            fut.set_exception(err)
                        else:
                            fut.set_result(result)
                except concurrent.futures.InvalidStateError:
                    pass
            try:
                item = self.intake.get_nowait()
            except queue.Empty:
                return

    def _hand_over(self, outputs: list[RequestOutput]) -> None:
        """Engine thread -> event loop, in the order handed over (one
        thread, and call_soon_threadsafe is FIFO): a request's token from
        a ragged step reaches its stream before its token from the decode
        step after it."""
        if self.loop is not None:
            self.loop.call_soon_threadsafe(self._deliver, outputs)

    def _deliver(self, outputs: list[RequestOutput]) -> None:
        for out in outputs:
            q = self.streams.get(out.request_id)
            if q is not None:
                q.put_nowait(out)

    def _deliver_error(self, rid: str, err: Exception) -> None:
        q = self.streams.get(rid)
        if q is not None:
            q.put_nowait(err)

    # -- async API ------------------------------------------------------------
    def _enqueued(self) -> tuple:
        """(stamp, engine step) of an add reaching the intake queue: the
        `enqueued` stamp of the request's time to first token."""
        clock = self.engine.clock
        return clock.now(), clock.step_num

    async def generate(
        self,
        prompt_token_ids: Seq[int],
        sampling: SamplingParams,
        request_id: Optional[str] = None,
        adapter_slot: int = 0,
        tenant: str = "anonymous",
    ) -> AsyncIterator[RequestOutput]:
        rid = request_id or f"req-{uuid.uuid4().hex[:16]}"
        q: asyncio.Queue = asyncio.Queue()
        self.streams[rid] = q
        self.intake.put(
            ("add", (rid, list(prompt_token_ids), sampling, adapter_slot,
                     tenant, self._enqueued()))
        )
        async for item in self._consume(rid, q):
            yield item

    async def admit_batch(
        self, requests: list
    ) -> list[AsyncIterator[RequestOutput]]:
        """Atomically admit requests (rid, prompt_ids, sampling,
        adapter_slot[, tenant]) on the engine thread — all-or-nothing.

        Unlike generate(), which enqueues the add and surfaces admission
        failures later on the stream, this waits for admission to complete
        BEFORE the caller commits to a response. A failure on any request
        aborts the already-added siblings, deregisters every stream, and
        re-raises — so the server can map grammar-bank exhaustion /
        vocab-infeasible grammars to clean HTTP statuses instead of
        mid-flight errors, and no slot can be stolen between a pre-check
        and the add (r3 review: check-vs-reserve race)."""
        qs: dict[str, asyncio.Queue] = {}
        for rid, *_ in requests:
            q: asyncio.Queue = asyncio.Queue()
            qs[rid] = q
            self.streams[rid] = q  # registered first: no output dropped

        enqueued = self._enqueued()

        def add_all(eng):
            added = []
            try:
                for req in requests:
                    rid, ids, sp, slot = req[:4]
                    tenant = req[4] if len(req) > 4 else "anonymous"
                    eng.add_request(rid, prompt_token_ids=list(ids),
                                    sampling=sp, adapter_slot=slot,
                                    tenant=tenant, enqueued=enqueued)
                    added.append(rid)
            except Exception:
                for r in added:
                    eng.abort_request(r)
                raise

        try:
            await self.run_on_engine(add_all)
        except BaseException:
            # BaseException: asyncio.CancelledError (client disconnect
            # mid-admission) must ALSO deregister the streams and abort the
            # admitted rids — otherwise they run with no consumer forever.
            # The abort intake items are queued after the add_all call item,
            # so the worker always processes them in order.
            for rid in qs:
                self.streams.pop(rid, None)
                self.abort(rid)
            raise
        return [self._consume(rid, q) for rid, q in qs.items()]

    async def attach_spliced(
        self,
        request_id: str,
        prompt_token_ids: Seq[int],
        first_token: int,
        sampling: SamplingParams,
        blocks: list[int],
        adapter_slot: int = 0,
        tenant: str = "anonymous",
    ) -> AsyncIterator[RequestOutput]:
        """Splice a pushed P→D transfer in as a decode-ready sequence
        (engine.splice_request) and return its output stream. Mirrors
        admit_batch: the stream is registered before the engine-thread
        splice so no output is dropped, and any failure (no decode slot,
        bad lengths, cancellation) deregisters the stream and re-raises —
        block ownership stays with the caller on failure."""
        q: asyncio.Queue = asyncio.Queue()
        self.streams[request_id] = q

        enqueued = self._enqueued()

        def do_splice(eng):
            eng.splice_request(request_id, list(prompt_token_ids),
                               first_token, sampling, blocks,
                               adapter_slot=adapter_slot, tenant=tenant,
                               enqueued=enqueued)

        try:
            await self.run_on_engine(do_splice)
        except BaseException:
            self.streams.pop(request_id, None)
            raise
        return self._consume(request_id, q)

    async def _consume(
        self, rid: str, q: asyncio.Queue
    ) -> AsyncIterator[RequestOutput]:
        try:
            while True:
                item = await q.get()
                if isinstance(item, Exception):
                    raise item
                yield item
                if item.finished:
                    return
        finally:
            self.streams.pop(rid, None)

    def abort(self, request_id: str) -> None:
        self.intake.put(("abort", request_id))

    async def run_on_engine(self, fn):
        """Run fn(engine) on the device-owning thread (KV export/import and
        anything else touching device state must not race the step loop)."""
        import concurrent.futures

        fut: concurrent.futures.Future = concurrent.futures.Future()
        self.intake.put(("call", (fn, fut)))
        return await asyncio.wrap_future(fut)

    # -- sleep mode (reference: /sleep /wake_up /is_sleeping proxying,
    #    src/vllm_router/services/request_service/request.py:1027-1114) ------
    async def sleep(self, level: int = 1) -> None:
        self.paused = True
        await self.run_on_engine(lambda eng: eng.sleep_mode(level))

    async def wake_up(self) -> None:
        await self.run_on_engine(lambda eng: eng.wake_mode())
        self.paused = False

    @property
    def is_sleeping(self) -> bool:
        return self.paused
