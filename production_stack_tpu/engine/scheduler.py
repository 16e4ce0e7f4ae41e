"""Continuous-batching scheduler (vLLM-semantics, TPU-shaped).

Token-budget scheduling, one mixed batch a step, in order:

1. **Admit**: move waiting sequences into decode slots while slots and KV
   blocks last, reusing prefix-cached blocks on admission.
2. **Decode rows**: every decodable sequence claims one stream token (one
   more for each granted draft), growing block tables; if the pool is
   exhausted, preempt the youngest sequence (free blocks, recompute
   later) — vLLM-style recompute preemption.
3. **Prefill chunks**: FCFS (or per-tenant fair share) chunks fill
   whatever budget decode left; ``max_num_batched_tokens`` is the only
   shape knob.

The engine packs the batch into a single ragged dispatch, as wide as the
narrowest of ``SchedulerConfig.ragged_stream_widths`` that holds it: the
width follows from what was scheduled here and never bears on it. A step
of decode rows alone goes to the fused decode program.

The scheduler is pure host-side control plane: it never touches device
arrays, it only decides. Counters here feed ``vllm:num_requests_running/
waiting`` (reference contract: src/vllm_router/stats/engine_stats.py:63-76).
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Optional

from production_stack_tpu.engine.config import CacheConfig, SchedulerConfig
from production_stack_tpu.engine.kv_cache import PrefixCachingBlockAllocator
from production_stack_tpu.engine.sequence import Sequence, SequenceStatus


class SchedulerQueueFull(Exception):
    """Raised by ``Scheduler.add`` when the waiting queue is at
    ``max_queue_len`` — the server maps it to 429 + Retry-After so the
    router fails over / backs off instead of piling work onto an
    overloaded engine."""


@dataclasses.dataclass
class ScheduledPrefill:
    seq: Sequence
    chunk_start: int  # == seq.num_computed_tokens
    chunk_len: int


@dataclasses.dataclass
class SchedulerOutput:
    prefills: list[ScheduledPrefill] = dataclasses.field(default_factory=list)
    decodes: list[Sequence] = dataclasses.field(default_factory=list)
    preempted: list[Sequence] = dataclasses.field(default_factory=list)

    @property
    def is_empty(self) -> bool:
        return not self.prefills and not self.decodes


class Scheduler:
    def __init__(self, sched: SchedulerConfig, cache: CacheConfig,
                 num_blocks: int, max_model_len: int = 1 << 30,
                 recurrent_state: bool = False, window: int = 0,
                 window_blocks: int = 0):
        self.config = sched
        self.cache_config = cache
        self.max_model_len = max_model_len
        # a model with recurrent layers: prefix lookups are served as
        # misses (see PrefixCachingBlockAllocator.bypass_prefix)
        self.recurrent_state = recurrent_state
        # ... or window layers that hold the last ``window`` rows alone: a
        # hit would be exact only where they still hold the rows below it,
        # so every lookup is a miss there too
        self.bypass_prefix = recurrent_state or bool(window)
        self.allocator = PrefixCachingBlockAllocator(
            num_blocks, cache.block_size, cache.enable_prefix_caching,
            bypass_prefix=self.bypass_prefix,
        )
        # a model whose window binds: its window layers' blocks come from a
        # pool of their own, taken as a sequence's rows reach them and
        # given back once no row to come can see them (``_trim_window``)
        self.window = window
        self.window_allocator = (
            PrefixCachingBlockAllocator(window_blocks, cache.block_size,
                                        bypass_prefix=True)
            if window else None)
        # window blocks live sequences gave back (``_trim_window``), and
        # the times a sequence found the window pool dry (the pool's size
        # rules it out: kv_cache.window_pool_blocks)
        self.window_blocks_released = 0
        self.window_block_waits = 0
        # sequences sent back to the queue to be recomputed (a pool dry)
        self.preemptions = 0
        self.waiting: collections.deque[Sequence] = collections.deque()
        self.seqs: dict[str, Sequence] = {}  # admitted, not finished
        self.free_slots = list(range(sched.max_num_seqs - 1, -1, -1))
        # invoked right after a sequence is admitted, before its first chunk
        # is scheduled. The tiered-KV engine starts an async warm-tier
        # prefix fetch here and may park the sequence in PREFETCHING —
        # scheduling gates prefill on PREFILLING and decode on
        # RUNNING, so a parked sequence holds its slot and blocks but
        # consumes no budget until the engine flips it back
        self.admission_hook = None
        # the engine's step number (StepClock.step_num), set by the engine
        # before each schedule(): stamped on a sequence beside admit_time,
        # which is read from the engine's clock (StepClock.now: the engine
        # sets it) so that it subtracts from the engine's other stamps
        self.step_num = 0
        self.now = time.monotonic
        # set by the engine when speculative decoding is on: returns the
        # draft width to reserve for a decode row (0 = ineligible or cold;
        # see spec.SpecController). The scheduler charges 1 + grant stream
        # tokens for the row and reserves KV blocks for the whole span.
        self.spec_grant_fn = None
        # brownout stage 1 (engine/overload.py): drafts are optional work,
        # so under sustained pressure grants go to zero before anything
        # user-visible degrades
        self.spec_shed = False
        self.spec_shed_count = 0  # decode rows whose grant was suppressed
        # -- per-tenant fair share (config.fair_share) -----------------------
        # carried DRR credit per tenant, in stream tokens: a bursty tenant
        # whose quantum outran its pending work this dispatch keeps the
        # remainder (capped at one full budget) instead of forfeiting it
        self._deficits: dict[str, float] = {}
        # stride-scheduling virtual pass per tenant for the weighted-fair
        # admission dequeue (lowest pass admits next; +1/weight per admit)
        self._admit_pass: dict[str, float] = {}
        # recent queue-exit stamps: drain rate for the derived Retry-After
        # on admission-queue 429s (satellite of the overload plane)
        self._admit_stamps: collections.deque[float] = collections.deque(
            maxlen=256)

    # -- queue management ---------------------------------------------------
    def add(self, seq: Sequence) -> None:
        if (self.config.max_queue_len > 0
                and len(self.waiting) >= self.config.max_queue_len):
            raise SchedulerQueueFull(
                f"waiting queue full ({len(self.waiting)} >= "
                f"{self.config.max_queue_len})")
        self.waiting.append(seq)

    def abort(self, request_id: str) -> Optional[Sequence]:
        for q in (list(self.waiting),):
            for s in q:
                if s.request_id == request_id:
                    self.waiting.remove(s)
                    s.status = SequenceStatus.FINISHED_ABORTED
                    return s
        s = self.seqs.get(request_id)
        if s is not None:
            self._release(s)
            s.status = SequenceStatus.FINISHED_ABORTED
            return s
        return None

    @property
    def num_waiting(self) -> int:
        return len(self.waiting)

    @property
    def num_free_blocks(self) -> int:
        """Reusable KV blocks (free pool + evictable cached); the
        deadline/disconnect tests assert this returns to its
        pre-request baseline after an abort."""
        return self.allocator.num_free_blocks

    @property
    def num_running(self) -> int:
        return len(self.seqs)

    @property
    def num_prefetching(self) -> int:
        """Admitted sequences parked on an in-flight warm-tier fetch."""
        return sum(1 for s in self.seqs.values()
                   if s.status is SequenceStatus.PREFETCHING)

    def has_work(self) -> bool:
        return bool(self.waiting or self.seqs)

    def live_request_ids(self) -> list[str]:
        """Every request id the scheduler still holds state for (waiting
        or running). The drain straggler-abort and step-failure recovery
        paths iterate this to free KV for all of them."""
        return [s.request_id for s in list(self.waiting)] + list(self.seqs)

    def _decode_exhausted(self, seq: Sequence) -> bool:
        """The dispatches launched so far sample the last token the
        sequence may have (the row at position p samples token p + 1, and
        the last one sampled is never fed back)."""
        bound = min(
            seq.num_prompt_tokens + seq.sampling.max_tokens,
            self.max_model_len,
        )
        return seq.num_computed_tokens >= bound - 1

    # -- internals ------------------------------------------------------------
    def _release(self, seq: Sequence) -> None:
        """Return a sequence's blocks and slot to the pools."""
        if seq.block_ids:
            seq.released_block_ids = list(seq.block_ids)
            self.allocator.free_blocks(seq.block_ids)
            seq.block_ids = []
        if seq.window_block_ids:
            self.window_allocator.free_blocks(
                seq.window_block_ids[seq.window_released:])
            seq.window_block_ids, seq.window_released = [], 0
        if seq.slot >= 0:
            self.free_slots.append(seq.slot)
            seq.slot = -1
        self.seqs.pop(seq.request_id, None)

    def finish(self, seq: Sequence, status: SequenceStatus) -> None:
        """Mark finished; full blocks stay content-addressed in the allocator
        so the next conversation round prefix-hits this context (the
        multi-round-QA KV-reuse win the reference gets from LMCache).

        Only positions < num_computed_tokens hold valid KV: the final
        sampled token was never fed back (single-step), and under
        speculative decoding rejected drafts leave garbage in the tail
        slots — committing a block containing such a position would
        content-address wrong KV for future prefix matches."""
        n_valid = min(len(seq.token_ids), seq.num_computed_tokens)
        self.allocator.commit_full_blocks(
            seq.token_ids[:n_valid], seq.block_ids
        )
        self._release(seq)
        try:
            # a seq can finish while PREEMPTED (its deferred prefill token
            # hit a stop after the scheduler re-queued it) — it must leave
            # the waiting deque or _try_admit would resurrect a finished
            # request and generate it again
            self.waiting.remove(seq)
        except ValueError:
            pass
        seq.status = status

    def _preempt(self, victim: Sequence) -> None:
        self.preemptions += 1
        self._release(victim)
        victim.status = SequenceStatus.PREEMPTED
        victim.num_computed_tokens = 0
        victim.num_cached_tokens = 0
        self.waiting.appendleft(victim)

    def _trim_window(self, seq: Sequence) -> None:
        """Give back the window blocks wholly below the rows that the next
        row to compute, at ``num_computed_tokens``, can see. Safe while an
        earlier step that reads them is in flight: whoever writes them
        next is dispatched after it."""
        dead = min((seq.num_computed_tokens - (self.window - 1))
                   // self.cache_config.block_size,
                   len(seq.window_block_ids))
        if dead > seq.window_released:
            self.window_allocator.free_blocks(
                seq.window_block_ids[seq.window_released:dead])
            self.window_blocks_released += dead - seq.window_released
            seq.window_released = dead

    def _extend(self, seq: Sequence, target: int) -> bool:
        """Blocks of both kinds for tokens ``[0, target)``; False where a
        pool is dry (what was taken stays with the sequence)."""
        bs = self.cache_config.block_size
        pools = [(self.allocator, seq.block_ids)]
        if self.window:
            self._trim_window(seq)
            pools.append((self.window_allocator, seq.window_block_ids))
        for allocator, ids in pools:
            while len(ids) * bs < target:
                bid = allocator.append_block()
                if bid is None:
                    self.window_block_waits += (
                        allocator is self.window_allocator)
                    return False
                ids.append(bid)
        return True

    def _next_waiting(self) -> Sequence:
        """The sequence the admission loop should try next.

        FIFO head, unless fair-share is on AND at least two tenants are
        waiting: then stride scheduling picks the per-tenant FCFS head
        whose tenant has the lowest virtual pass (pass advances by
        1/weight per admission), so a flooding tenant's backlog queues
        behind everyone else instead of monopolising the queue head. A
        tenant first seen mid-flight joins at the current pass floor —
        immediately competitive, never owed retroactive credit. With one
        tenant (or fairness off) this IS the FIFO head, bit-identically.
        """
        if not self.config.fair_share:
            return self.waiting[0]
        heads: dict[str, Sequence] = {}
        for s in self.waiting:  # deque order = FCFS within each tenant
            if s.tenant not in heads:
                heads[s.tenant] = s
        if len(heads) < 2:
            return self.waiting[0]
        floor = min(self._admit_pass.get(t, 0.0) for t in heads)
        pick = min(heads, key=lambda t: (
            max(self._admit_pass.get(t, floor), floor), t))
        return heads[pick]

    def _note_admitted(self, seq: Sequence) -> None:
        """Post-admission bookkeeping: drain-rate stamp + stride pass."""
        self._admit_stamps.append(time.monotonic())
        if not self.config.fair_share:
            return
        t = seq.tenant
        floor = min((self._admit_pass.get(s.tenant, 0.0)
                     for s in self.waiting), default=0.0)
        p = max(self._admit_pass.get(t, floor), floor)
        self._admit_pass[t] = p + 1.0 / self.config.tenant_weight(t)
        if len(self._admit_pass) > 512:  # bound churn: keep live tenants
            live = ({s.tenant for s in self.waiting}
                    | {s.tenant for s in self.seqs.values()})
            self._admit_pass = {k: v for k, v in self._admit_pass.items()
                                if k in live}

    def admission_drain_rate(self, now: Optional[float] = None) -> float:
        """Recent queue-exit rate in admissions/sec (0.0 = unknown)."""
        if len(self._admit_stamps) < 2:
            return 0.0
        now = time.monotonic() if now is None else now
        span = now - self._admit_stamps[0]
        if span <= 0:
            return 0.0
        return len(self._admit_stamps) / span

    def retry_after_hint(self, floor: float = 1.0,
                         ceiling: float = 60.0,
                         now: Optional[float] = None) -> float:
        """Seconds until the waiting queue plausibly has room: current
        depth over the measured drain rate, clamped to [floor, ceiling].
        Falls back to ``floor`` (the configured constant) before any
        drain history exists — the 429 Retry-After header derives from
        THIS, so the router's breaker/backoff paces clients
        proportionally to real congestion, not a fixed guess."""
        rate = self.admission_drain_rate(now)
        if rate <= 0.0:
            return floor
        return min(max(len(self.waiting) / rate, floor), ceiling)

    def tenant_loads(self) -> dict[str, float]:
        """Waiting + admitted sequence count per tenant — the load view
        the stage-3 brownout shed set is computed from."""
        loads: dict[str, float] = {}
        for s in list(self.waiting):
            loads[s.tenant] = loads.get(s.tenant, 0.0) + 1.0
        for s in self.seqs.values():
            loads[s.tenant] = loads.get(s.tenant, 0.0) + 1.0
        return loads

    def fair_share_snapshot(self) -> dict:
        """Carried DRR deficits + stride passes, for the
        ``vllm:fair_share_deficit{tenant}`` gauge (folded at export)."""
        return {
            "enabled": bool(self.config.fair_share),
            "deficits": dict(self._deficits),
            "admit_pass": dict(self._admit_pass),
        }

    def _try_admit(self) -> None:
        bs = self.cache_config.block_size
        while self.waiting and self.free_slots:
            seq = self._next_waiting()
            if self.window and (
                    self.window_allocator.num_free_blocks * bs
                    < min(len(seq.token_ids),
                          self.config.max_num_batched_tokens)):
                self.window_block_waits += 1
                break  # no window blocks for its first chunk
            got = self.allocator.allocate_sequence(seq.token_ids)
            if got is None:
                break
            if seq is self.waiting[0]:
                self.waiting.popleft()
            else:
                self.waiting.remove(seq)
            seq.block_ids, cached = got
            seq.num_cached_tokens = cached
            seq.num_computed_tokens = cached
            seq.slot = self.free_slots.pop()
            seq.status = SequenceStatus.PREFILLING
            # queue-exit stamp; kept across preemption-readmits so
            # queue_time measures the FIRST wait (the user-visible one)
            if seq.admit_time is None:
                seq.admit_time = self.now()
                seq.admit_step = self.step_num
            self.seqs[seq.request_id] = seq
            self._note_admitted(seq)
            if self.admission_hook is not None:
                self.admission_hook(seq)

    def splice(self, seq: Sequence) -> None:
        """Register a decode-ready sequence that was prefilled ELSEWHERE
        (disagg P→D handoff): its KV blocks were landed by /kv/recv, its
        first token is already in ``output_token_ids`` and
        ``num_computed_tokens`` covers the whole prompt, so
        ``prefill_done`` holds and ``schedule``/``_grow_decodes``
        pick it up as a decode row on the next step — no pass through the
        waiting queue, no re-prefill. The caller owns the blocks until
        this returns; afterwards the normal finish/abort paths release
        them. Raises ``SchedulerQueueFull`` when no decode slot is free
        (the server degrades to the re-prefill path)."""
        if not self.free_slots:
            raise SchedulerQueueFull("no decode slot free for spliced seq")
        seq.slot = self.free_slots.pop()
        seq.status = SequenceStatus.RUNNING
        if seq.admit_time is None:
            seq.admit_time = self.now()
            seq.admit_step = self.step_num
        self.seqs[seq.request_id] = seq

    # -- the per-step decision ----------------------------------------------
    def schedule(self) -> SchedulerOutput:
        """Token-budget continuous batching (RTP-LLM-style): decode rows
        claim one stream token each, then FCFS prefill chunks fill
        whatever budget is left — one mixed batch per step, and
        ``max_num_batched_tokens`` as the ONLY shape knob (the ragged
        dispatch has no padded chunk dimension to round up to).

        With speculation on, each spec-eligible decode row is charged
        ``1 + grant`` stream tokens so drafts compete fairly with prefill
        chunks for the same budget."""
        out = SchedulerOutput()
        self._try_admit()
        out.decodes = self._grow_decodes(out)
        budget = self.config.max_num_batched_tokens - len(out.decodes)
        if self.spec_grant_fn is not None:
            budget = self._grant_spec_drafts(out, budget)
        ordered = sorted(self.seqs.values(), key=lambda s: s.arrival_time)
        if self.config.fair_share:
            pending_tenants = {s.tenant for s in ordered
                               if s.status is SequenceStatus.PREFILLING
                               and not s.prefill_done}
            if len(pending_tenants) >= 2:
                return self._fair_prefill(out, ordered, budget)
            # single tenant: fall through to the exact FCFS loop below —
            # the fairness-on fast path is bit-identical by construction
        for seq in ordered:
            if seq.status is not SequenceStatus.PREFILLING:
                continue
            if seq.prefill_done:
                # preemption-recompute whose context fully prefix-matched
                # on re-admission: nothing to compute, decodes next step
                seq.status = SequenceStatus.RUNNING
                continue
            if budget <= 0:
                break
            remaining = seq.prefill_target - seq.num_computed_tokens
            chunk = min(remaining, budget)
            if self.window and not self._extend(
                    seq, seq.num_computed_tokens + chunk):
                continue  # waits for window blocks (the pool's size rules
                # it out: kv_cache.window_pool_blocks)
            out.prefills.append(
                ScheduledPrefill(seq, seq.num_computed_tokens, chunk)
            )
            budget -= chunk
        return out

    def _fair_prefill(self, out: SchedulerOutput,
                      ordered: list[Sequence], budget: int) -> SchedulerOutput:
        """Deficit-round-robin split of the prefill budget across tenants
        (ROADMAP item 3). Each dispatch credits every tenant with pending
        prefill work a quantum of ``budget * weight/sum(weights)`` tokens
        on top of its carried deficit, serves quanta largest-deficit
        first, then redistributes whatever the light tenants couldn't use
        to tenants still pending — so the budget is always fully consumed
        when work exists (fairness never costs throughput, it only
        re-orders who prefills first). Chunks pack in global FCFS order
        bounded by each tenant's allocation, keeping intra-tenant order
        and the ragged dispatch shape identical to the FCFS path."""
        queues: dict[str, list[Sequence]] = {}
        for seq in ordered:
            if seq.status is not SequenceStatus.PREFILLING:
                continue
            if seq.prefill_done:
                seq.status = SequenceStatus.RUNNING
                continue
            queues.setdefault(seq.tenant, []).append(seq)
        # a tenant with no pending work banks no credit while idle —
        # idle time is not a claim on future capacity
        for t in list(self._deficits):
            if t not in queues:
                del self._deficits[t]
        if budget <= 0 or not queues:
            return out
        weight = self.config.tenant_weight
        work = {t: sum(s.prefill_target - s.num_computed_tokens for s in q)
                for t, q in queues.items()}
        wsum = sum(weight(t) for t in queues)
        for t in queues:
            self._deficits[t] = (self._deficits.get(t, 0.0)
                                 + budget * weight(t) / wsum)
        alloc = dict.fromkeys(queues, 0)
        left = budget
        # serve the fair quanta, largest carried deficit first (carries can
        # oversubscribe the budget; the longest-shorted tenant goes first)
        for t in sorted(queues, key=lambda t: (-self._deficits[t], t)):
            take = min(int(self._deficits[t]), work[t], left)
            if take > 0:
                alloc[t] = take
                self._deficits[t] -= take
                left -= take
        # unused share redistributes: quanta the light tenants couldn't
        # fill go to tenants still pending, weight-proportionally
        while left > 0:
            act = sorted(t for t in queues if work[t] - alloc[t] > 0)
            if not act:
                break
            rsum = sum(weight(t) for t in act)
            gave = 0
            for t in act:
                take = min(int(left * weight(t) / rsum),
                           work[t] - alloc[t], left - gave)
                alloc[t] += take
                gave += take
            if gave == 0:  # all shares rounded below one token
                alloc[act[0]] += 1
                gave = 1
            left -= gave
        # carried credit is capped at one full dispatch budget: a backlog
        # may be owed, but never more than one dispatch's worth
        cap = float(self.config.max_num_batched_tokens)
        for t in self._deficits:
            self._deficits[t] = min(self._deficits[t], cap)
        for seq in ordered:
            if (seq.status is not SequenceStatus.PREFILLING
                    or seq.prefill_done):
                continue
            quota = alloc.get(seq.tenant, 0)
            if quota <= 0:
                continue
            chunk = min(seq.prefill_target - seq.num_computed_tokens, quota)
            if self.window and not self._extend(
                    seq, seq.num_computed_tokens + chunk):
                continue
            out.prefills.append(
                ScheduledPrefill(seq, seq.num_computed_tokens, chunk)
            )
            alloc[seq.tenant] = quota - chunk
        return out

    def _grant_spec_drafts(self, out: SchedulerOutput, budget: int) -> int:
        """Reserve stream budget and KV blocks for speculative drafts.

        FCFS over the decode rows: each eligible row asks ``spec_grant_fn``
        for its adaptive width, gets it clamped to the remaining budget,
        and has blocks appended so positions ``num_computed .. num_computed
        + grant`` all have KV slots — drafts are no longer silently
        truncated at a block boundary the way the old batch-wide path
        clamped them. Draft capacity never preempts anyone (drafts are
        optional work); if the pool is dry the grant shrinks to whatever
        the current table holds. The final grant lands on ``seq.spec_grant``
        for the engine to propose against at pack time.

        Under brownout stage 1+ (``spec_shed``) every grant is zero:
        drafts are optional work, so their stream-budget share is the
        first thing reclaimed — rows still decode their one real token."""
        if self.spec_shed:
            for seq in out.decodes:
                seq.spec_grant = 0
            self.spec_shed_count += len(out.decodes)
            return budget
        bs = self.cache_config.block_size
        for seq in sorted(out.decodes, key=lambda s: s.arrival_time):
            seq.spec_grant = 0
            if budget <= 0:
                continue
            k = min(self.spec_grant_fn(seq), budget,
                    self.max_model_len - 1 - seq.num_computed_tokens)
            if k <= 0:
                continue
            target = seq.num_computed_tokens + 1 + k
            while len(seq.block_ids) * bs < target:
                bid = self.allocator.append_block()
                if bid is None:
                    break
                seq.block_ids.append(bid)
            k = min(k, len(seq.block_ids) * bs - seq.num_computed_tokens - 1)
            if k <= 0:
                continue
            seq.spec_grant = k
            budget -= k
        return budget

    def _grow_decodes(self, out: SchedulerOutput) -> list[Sequence]:
        """Collect every decodable sequence, growing block tables first so
        each has capacity for the next ``decode_horizon`` tokens
        (positions num_computed .. num_computed + horizon - 1); if the
        pool is exhausted, preempt the youngest sequence (free blocks,
        recompute later) — vLLM-style recompute preemption. A sequence
        whose already-dispatched tokens cover its completion bound is
        excluded: under deferred resolution its finish is still in
        flight, and a further dispatch would run past max_model_len's
        block table."""
        decodes = sorted(
            (s for s in self.seqs.values()
             if s.status is SequenceStatus.RUNNING
             and not self._decode_exhausted(s)),
            key=lambda s: s.slot,
        )
        horizon = self.config.decode_horizon
        survivors = []
        for seq in decodes:
            if seq.status is not SequenceStatus.RUNNING:
                continue  # preempted earlier in this same pass
            preempted_self = False
            # capacity past max_model_len is never consumed (the runner
            # drops KV writes there), so don't allocate blocks for it —
            # near the length cap the table row may have no slack
            target = min(seq.num_computed_tokens + horizon,
                         self.max_model_len)
            while not self._extend(seq, target):
                victim = self._pick_victim(exclude=seq)
                if victim is None:
                    # no one else to evict: preempt this sequence itself
                    self._preempt(seq)
                    out.preempted.append(seq)
                    preempted_self = True
                    break
                self._preempt(victim)
                out.preempted.append(victim)
                if victim in survivors:
                    survivors.remove(victim)
            if not preempted_self:
                survivors.append(seq)
        return survivors

    def _pick_victim(self, exclude: Sequence) -> Optional[Sequence]:
        candidates = [
            s
            for s in self.seqs.values()
            if s is not exclude and s.status is SequenceStatus.RUNNING
        ]
        if not candidates:
            return None
        return max(candidates, key=lambda s: s.arrival_time)
