"""LLMEngine: the synchronous engine core (scheduler + model runner).

``step()`` runs one scheduler decision on device and returns per-request
increments. The async server (engine/server.py) drives it from an executor
thread; tests and the benchmark drive it directly.

This layer is the TPU-native replacement for the vLLM engine the reference
stack assumes exists underneath it (SURVEY.md §7 step 1).
"""

from __future__ import annotations

import dataclasses
import os
import time
from collections import deque
from typing import Optional, Sequence as Seq

import jax
import numpy as np
from jax.sharding import Mesh

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.kv_cache import slot_mapping_for
from production_stack_tpu.engine.model_runner import ModelRunner
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.scheduler import Scheduler
from production_stack_tpu.engine.sequence import (
    RequestOutput,
    Sequence,
    SequenceStatus,
)
from production_stack_tpu.engine.tokenizer import get_tokenizer
from production_stack_tpu.engine.tracing import StartClock, StepClock
from production_stack_tpu.ops.kda import continues_one_row
from production_stack_tpu.ops.ragged_paged_attention_pallas import (
    count_walks,
    count_windows,
    q_tile_for,
)
from production_stack_tpu.parallel.mesh import build_mesh
from production_stack_tpu.tenancy import split_shares


# How long before the landing it foresees `LLMEngine._launch_ahead` asks
# the probe and queues the next decode program. It covers what the host
# takes from there to the program standing on the device (the thread's
# wake-up from its sleep, ~0.4 ms in the mean beside the server's loop,
# and the launch call, 0.6-0.7 ms) and how much later than the device
# ends a program the host sees it landed once the fetch follows a launch
# (0.5-0.8 ms), and no more: a request that reaches the intake inside the
# lead waits the queued program out, and those are the requests that had
# the shortest wait of all. Measured on one v5e in OLMoE's `decode-heavy`
# cell (PERF.md section 6, PR 58): 1.0 ms closes a quarter of the ~2 ms a
# prepared step stands between two decode programs when it launches at the
# landing, 2.0 ms three quarters, 2.5 ms nearly all of it, at 16 % of the
# arrivals behind a queued program and the median first token 2 % later.
DECODE_AHEAD_LEAD_S = 0.002
# the runs of one kind of decode dispatch `_landing_expected` looks back on
DECODE_RUNS_KEPT = 8


class GrammarBankFull(ValueError):
    """Every grammar-bank slot is referenced by a live request.

    A distinct exception type so the server can map admission failure to
    HTTP 429 (retryable) while other ValueErrors stay 400s."""


def _grammar_key(guided_regex, guided_json):
    """Cache key for a guided grammar — the ONE place it is derived, so
    admission and any availability checks can never desynchronize."""
    import json as _json

    if guided_regex is not None:
        return ("re", guided_regex)
    return ("json", _json.dumps(guided_json, sort_keys=True))


def _lp_row(lp: tuple, i: int):
    """One token's logprob entry from fetched (tok_lp, ids, lps) arrays:
    (token_logprob, [(token_id, logprob) * top-N])."""
    tok_lp, ids, lps = lp
    return (
        float(tok_lp[i]),
        [(int(t), float(v)) for t, v in zip(ids[i], lps[i])],
    )


class LLMEngine:
    # vllm:kv_prefetch_seconds histogram edges (an extra +Inf bucket is
    # implied; metrics.py renders the cumulative prometheus form)
    _PREFETCH_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                         1.0, 2.5, 5.0)

    def __init__(
        self,
        config: EngineConfig,
        mesh: Optional[Mesh] = None,
        params: Optional[dict] = None,
        num_blocks: Optional[int] = None,
        start: Optional[StartClock] = None,
    ):
        # where this replica's start goes (engine/tracing.py): `main()`'s
        # clock, which already holds `process` and `backend_open`, or one
        # of this engine's own; building the engine is one span of it,
        # `engine_build`, whose children the runner opens
        self.start = start if start is not None else StartClock()
        if config.perf.enabled:
            # before the first program is built: the weights' and the
            # pool's programs are builds too (kind `other`)
            from production_stack_tpu.engine.perf_accounting import (
                build_stages,
            )

            build_stages()
        with self.start.span("engine_build"):
            self._build(config, mesh, params, num_blocks)

    def _build(self, config: EngineConfig, mesh: Optional[Mesh],
               params: Optional[dict], num_blocks: Optional[int]) -> None:
        self.config = config
        self.mesh = mesh if mesh is not None else build_mesh(config.mesh)
        with self.start.span("tokenizer"):
            self.tokenizer = get_tokenizer(config.model.tokenizer)
        from production_stack_tpu.parallel.mesh import AXIS_SEQ, AXIS_STAGE

        for axis in (AXIS_STAGE, AXIS_SEQ):
            if self.mesh.shape[axis] > 1:
                raise ValueError(
                    f"a mesh with {axis}={self.mesh.shape[axis]} is not "
                    "supported: pipeline stages and ring prefill were "
                    "removed, shard a model over the tensor axis")
        if config.model.is_latent:
            ModelRunner._refuse_for_latent_cache(config, self.mesh)
        self.runner = ModelRunner(config, self.mesh, params, num_blocks,
                                  start=self.start)
        # where this thread's time goes, always on (engine/tracing.py);
        # the runner switches its own phases (snapshot, commit, launch)
        self.clock = self.runner.clock = StepClock()
        # where the model's window binds, the window layers' blocks are a
        # second pool with a block table of its own (engine/kv_cache.py)
        self.window = (config.model.sliding_window
                       if config.model.window_binds else 0)
        self.scheduler = Scheduler(
            config.scheduler, config.cache, self.runner.num_blocks,
            max_model_len=config.model.max_model_len,
            recurrent_state=config.model.has_recurrent_state,
            window=self.window,
            window_blocks=self.runner.window_blocks,
        )
        self.scheduler.now = self.clock.now
        # what the latent attention kernel of an MLA model was asked to
        # score (engine/tracing.py); None for every other model
        self.latent = None
        if config.model.is_latent:
            from production_stack_tpu.engine.tracing import LatentCounters
            from production_stack_tpu.ops.latent_paged_attention_pallas import (  # noqa: E501
                EXPAND_ROWS,
            )

            self.latent = LatentCounters(
                config.model.cache_layers, config.model.kv_bytes_per_token,
                expand_rows=EXPAND_ROWS if self.runner.use_pallas else None)
        # what the recurrent layers of a hybrid stack ran
        # (engine/tracing.py); None for every other model
        self.recurrent = None
        if config.model.has_recurrent_state:
            import logging

            from production_stack_tpu.engine.tracing import RecurrentCounters

            slots = config.scheduler.max_num_seqs
            self.recurrent = RecurrentCounters(
                config.model.num_recurrent_layers,
                config.model.recurrent_state_bytes(slots),
                kind=("mamba" if config.model.mamba_period
                      else "ssd" if config.model.ssd_heads
                      else "gdn" if config.model.gdn_heads else "kda"))
            logging.getLogger(__name__).info(
                "%s keeps recurrent state per decode slot (%d %s layers x "
                "%d slots, %.2f GB): prefix-cache lookups are served as "
                "misses, a preempted sequence recomputes from position 0",
                config.model.name, config.model.num_recurrent_layers,
                self.recurrent.kind, slots, self.recurrent.state_bytes / 1e9)
        # what the window layers' walks stream beside what their calls
        # would read without a window, and the cross-attention layers'
        # calls (engine/tracing.py); None where no window binds
        self.window_counters = None
        if self.window:
            from production_stack_tpu.engine.tracing import WindowCounters

            self.window_counters = WindowCounters(
                config.model, config.cache.block_size)
        # the ragged step (ops/ragged_paged_attention_pallas.py): the
        # scheduler mixes decode rows and prefill chunks into one
        # token-budget batch, packed here into a single (1, T) stream
        self._pending_ragged = None
        sched = config.scheduler
        if sched.max_num_batched_tokens < sched.max_num_seqs:
            raise ValueError(
                "the ragged step needs max_num_batched_tokens "
                f"({sched.max_num_batched_tokens}) >= max_num_seqs "
                f"({sched.max_num_seqs}): every decode row claims one "
                "stream token per step"
            )
        T = sched.max_num_batched_tokens
        self._r_tokens = np.zeros((1, T), np.int32)
        self._r_positions = np.full((1, T), -1, np.int32)
        self._r_slot_mapping = np.full(T, -1, np.int32)
        self._r_window_slot_mapping = np.full(T, -1, np.int32)
        self._r_adapter_ids = np.zeros(T, np.int32)
        self._r_cu = np.zeros(sched.max_num_seqs + 1, np.int32)
        self._r_last_idx = np.zeros(sched.max_num_seqs, np.int32)
        self._r_sample_mask = np.zeros(sched.max_num_seqs, np.float32)
        from production_stack_tpu.engine.kv_cache import (
            kv_cache_bytes_per_block,
        )
        from production_stack_tpu.engine.kv_offload import (
            maybe_make_remote,
            maybe_make_store,
        )

        self._kv_bytes_per_block = kv_cache_bytes_per_block(
            config.model, config.cache)
        self.host_kv = maybe_make_store(
            config.cache, bytes_per_block=self._kv_bytes_per_block)
        self.remote_kv = maybe_make_remote(config.cache)
        # tiered-KV closed loop (engine/kv_offload.py): admission starts an
        # async warm-tier prefix fetch (the sequence parks in PREFETCHING),
        # HBM eviction demotes to host, host eviction demotes to remote.
        # Per-tier traffic is byte-accounted from HBM's perspective:
        # direction "in" = promotion into the pool, "out" = demotion/offload
        self._prefetcher = None
        self.hbm_demotions = 0
        # brownout stage 2+ (engine/overload.py): stop LAUNCHING new
        # warm-tier prefetches; admitted sequences fall back to a plain
        # cold prefill (correct, just not prefetched)
        self.prefetch_paused = False
        self.prefetch_shed_count = 0
        self.prefetch_blocks = 0
        self.prefetch_count = 0
        self.prefetch_seconds_sum = 0.0
        self.prefetch_stall_seconds = 0.0
        self.prefetch_hist = [0] * (len(self._PREFETCH_BUCKETS) + 1)
        self.tier_bytes = {("host", "in"): 0, ("host", "out"): 0,
                           ("remote", "in"): 0, ("remote", "out"): 0}
        if self.host_kv is not None or self.remote_kv is not None:
            from production_stack_tpu.engine.kv_offload import KVPrefetcher

            self._prefetcher = KVPrefetcher(
                self.host_kv, self.remote_kv, config.cache.block_size,
                config.cache.kv_prefetch_workers)
            self.scheduler.admission_hook = self._start_tier_prefetch
        self._wire_tier_hooks()
        B = config.scheduler.max_num_seqs
        M = self.runner.max_blocks_per_seq
        # persistent decode-batch host arrays (rewritten in place each step)
        self._tokens = np.zeros(B, np.int32)
        self._positions = np.zeros(B, np.int32)
        self._block_tables = np.zeros((B, M), np.int32)
        self._window_tables = np.zeros((B, M), np.int32)
        self._context_lens = np.zeros(B, np.int32)
        self._slot_mapping = np.full(B, -1, np.int32)
        self._window_slot_mapping = np.full(B, -1, np.int32)
        self._temps = np.zeros(B, np.float32)
        self._top_ps = np.ones(B, np.float32)
        self._top_ks = np.full(B, -1, np.int32)
        self._seeds = np.zeros(B, np.uint32)
        self._steps = np.zeros(B, np.int32)
        self._presence = np.zeros(B, np.float32)
        self._frequency = np.zeros(B, np.float32)
        self._adapter_ids = np.zeros(B, np.int32)
        from production_stack_tpu.engine.sampling import MAX_TOKEN_CONTROLS

        self._ctrl_ids = np.full((B, MAX_TOKEN_CONTROLS), -1, np.int32)
        self._ctrl_vals = np.zeros((B, MAX_TOKEN_CONTROLS), np.float32)
        self._ctrl_mode = np.zeros(B, np.int32)
        self._g_ids = np.full(B, -1, np.int32)
        self._g_states = np.zeros(B, np.int32)
        # constrained decoding: compiled grammars keyed by pattern, device
        # bank slots refcounted; evicted (refs == 0) only when slots run out
        self._grammar_cache: dict = {}
        self._grammar_by_slot: dict = {}
        self._grammar_free = list(range(config.max_grammars - 1, -1, -1))
        self._token_bytes = None  # lazy per-vocab byte images
        self._count_reset_slots: list[Sequence] = []
        self._slot_seq: dict[int, Sequence] = {}
        # the decode dispatch in flight (`_run_decode`): the next one, over
        # the same slots or fewer, takes its input tokens DEVICE-side (the
        # last sampled row, un-fetched), and is launched when this one's
        # (K, B) samples have landed or a lead before (`_launch_ahead`:
        # this is then the one queued, and the one it stands behind is
        # fetched before the step returns). Stop checks come after that
        # launch:
        # the surplus tokens a finished sequence generates
        # land only in its own uncommitted tail blocks (prefix hashes cover
        # full blocks of host-side token_ids), and any dispatch issued after
        # the blocks are released executes later in device program order —
        # so deferred stops can't corrupt reused or cached blocks.
        self._pending_decode = None
        # n-gram speculative decoding (engine/spec.py): drafts ride the
        # ragged stream as short prefill-shaped spans and verification is
        # fused into the one ragged program (no standalone verify).
        # Eligibility is per sequence and the draft width adapts via
        # acceptance EWMA.
        k = config.scheduler.spec_ngram_k
        self._spec = None
        if k > 0:
            from production_stack_tpu.engine.spec import SpecController

            self._spec = SpecController(k_max=k)
            self.scheduler.spec_grant_fn = self._spec_grant_fn
            # stream indices of each slot's draft positions, rides EVERY
            # ragged dispatch so verify-bearing steps share the one
            # steady-state compile signature with plain ones
            self._r_verify_idx = np.zeros((B, k), np.int32)
        # metrics
        self.total_prompt_tokens = 0
        self.total_output_tokens = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_steps = 0  # spec row-steps (one per verified span)
        self.spec_step_tokens = 0  # tokens those row-steps emitted
        self.aborted_seqs = 0  # cancelled/expired, KV freed early
        self.spliced_seqs = 0  # pushed P→D transfers attached decode-ready
        # ragged dispatch accounting: live packed tokens vs the budget is
        # the padding-waste signal; narrow: the dispatches that ran under
        # the budget's width (SchedulerConfig.ragged_stream_widths)
        self.ragged_dispatches = 0
        self.ragged_narrow_dispatches = 0
        self.ragged_live_tokens = 0
        # (tile, span) context walks the ragged attention kernel makes for
        # the dispatched span offsets, and those it runs on its narrow row
        # block (ops/ragged_paged_attention_pallas.count_walks)
        self.ragged_attn_walks = 0
        self.ragged_attn_narrow_walks = 0
        # context windows those walks stream, and those a full-tile walk
        # runs through the kernel's interior body (count_windows)
        self.ragged_attn_windows = 0
        self.ragged_attn_interior_windows = 0
        self.decode_dispatches = 0  # decode dispatches launched
        # attention calls those dispatches made (fused iterations x cache
        # layers each), and those in which the Pallas decode kernel scored
        # a window from the slab as stored (ops/paged_attention_pallas.
        # decode_slab_path, decided by the runner for its per-shard
        # geometry, the window layers' calls apart)
        self.decode_attn_calls = 0
        self.decode_attn_slab_calls = 0
        # of them, launched from inputs built and committed while the
        # dispatch before was still running (`_run_decode`)
        self.decode_prepared_launches = 0
        # and of those, queued behind the dispatch before a lead ahead of
        # its landing (`_launch_ahead`), with the time from such a launch
        # to the landing it went ahead of, summed and at its largest
        self.decode_ahead_launches = 0
        self.decode_ahead_lead_seconds = 0.0
        self.decode_ahead_lead_max_seconds = 0.0
        # asked before a decode program joins the device's queue: has
        # something arrived that the next step should take in first? At a
        # ragged step's landing (`_step`), at a decode dispatch's landing
        # with the next one prepared (`_arrival_first`), and a lead before
        # such a landing (`_launch_ahead`). The async worker sets it (its
        # intake queue is not empty); None, as when step() is driven by
        # hand: nothing is ever pending
        self.arrival_probe = None
        # sleeps until a stamp of the step clock, for `_launch_ahead`. The
        # async worker sets it beside the probe; None, as when step() is
        # driven by hand: a prepared step is launched at the landing
        self.landing_wait = None
        # (`like` of the last decode dispatch landed, how long the last
        # few like it ran, each from its launch, or from the landing of
        # the one it was queued behind, to its landing): when the next
        # one like it will land (`_landing_expected`)
        self._decode_runs = (None, ())
        # the last landing at which the next decode program already stood
        # queued: a request that reached the intake before it would have
        # had its ragged step launched at that landing
        self._queued_landing_t = 0.0
        self.intake_requests = 0  # taken in from the intake queue
        self.arrivals_behind_queued_decode = 0  # of them, before such a landing
        # ragged steps at whose landing something had arrived, so that no
        # decode program was launched after them (`_step`)
        self.ragged_landing_arrivals = 0
        # goodput accounting + compile tracking (perf_accounting.py)
        self.perf = None
        if config.perf.enabled:
            from production_stack_tpu.engine.perf_accounting import (
                PerfAccountant,
            )

            self.perf = PerfAccountant.from_runner(config, self.runner)
            self.runner.install_compile_observer(self._on_compile)
        # the weights' and the pool's programs were dispatched and their
        # spans closed at the host's return: how long the device still
        # runs them is this one wait (the first request needs both)
        with self.start.span("device_drain"):
            jax.block_until_ready((self.runner.params, self.runner.kv))

    def _on_compile(self, kind: str, bucket: str, seconds: float,
                    build: dict) -> None:
        # the step clock names a slow step's cause `compile` after this
        self.clock.note_build(build)
        self.perf.on_compile(kind, bucket, seconds, build)

    # -- request intake ------------------------------------------------------
    def add_request(
        self,
        request_id: str,
        prompt: Optional[str] = None,
        prompt_token_ids: Optional[Seq[int]] = None,
        sampling: Optional[SamplingParams] = None,
        adapter_slot: int = 0,
        tenant: str = "anonymous",
        enqueued: Optional[tuple] = None,
    ) -> Sequence:
        """``enqueued``: (stamp, engine step) at which the event loop put
        this add on the intake queue (AsyncEngine), where one did."""
        if prompt_token_ids is None:
            assert prompt is not None, "prompt or prompt_token_ids required"
            prompt_token_ids = self.tokenizer.encode(prompt)
        if not prompt_token_ids:
            raise ValueError("empty prompt")
        if len(prompt_token_ids) > self.config.model.max_model_len - 1:
            raise ValueError(
                f"prompt length {len(prompt_token_ids)} exceeds max_model_len "
                f"{self.config.model.max_model_len}"
            )
        sampling = (sampling or SamplingParams()).clamped(
            self.config.model.max_model_len, len(prompt_token_ids)
        )
        if sampling.logprobs is not None:
            from production_stack_tpu.engine.sampling import MAX_LOGPROBS

            if not 0 <= sampling.logprobs <= MAX_LOGPROBS:
                raise ValueError(
                    f"logprobs must be in [0, {MAX_LOGPROBS}]"
                )
        if sampling.seed is None:
            # unseeded sampling must be nondeterministic (OpenAI/vLLM
            # semantics): identical concurrent prompts must not draw the
            # same Gumbel noise. User-provided seeds (including 0) are kept.
            sampling = dataclasses.replace(
                sampling,
                seed=int.from_bytes(os.urandom(4), "little"),
            )
        from production_stack_tpu.engine.sampling import make_token_controls

        seq = Sequence(request_id, list(prompt_token_ids), sampling,
                       adapter_slot=adapter_slot,
                       tenant=tenant or "anonymous",
                       token_ctrl=make_token_controls(
                           sampling, self.config.model.vocab_size))
        self._stamp_arrival(seq, enqueued)
        if sampling.guided_regex is not None or sampling.guided_json is not None:
            ent = self._acquire_grammar(sampling)
            seq.grammar_slot = ent["slot"]
            seq.fsm = ent["fsm"]
            seq.fsm_state = 0
        self.scheduler.add(seq)
        self.total_prompt_tokens += len(prompt_token_ids)
        return seq

    def _stamp_arrival(self, seq: Sequence, enqueued: Optional[tuple]) -> None:
        seq.arrival_time = self.clock.now()
        seq.arrival_step = self.clock.step_num
        seq.arrival_after = self.clock.last_wait
        if enqueued is not None:
            seq.enqueue_time, seq.enqueue_step = enqueued
            self.intake_requests += 1
            self.arrivals_behind_queued_decode += (
                seq.enqueue_time < self._queued_landing_t)

    def abort_request(self, request_id: str) -> bool:
        seq = self.scheduler.abort(request_id)
        if seq is not None and seq.slot in self._slot_seq:
            del self._slot_seq[seq.slot]
        if seq is not None:
            self._release_grammar(seq)
            self.aborted_seqs += 1
        return seq is not None

    # -- constrained decoding (engine/grammar.py) ---------------------------
    def _acquire_grammar(self, sampling: SamplingParams) -> dict:
        from production_stack_tpu.engine import grammar as G

        key = _grammar_key(sampling.guided_regex, sampling.guided_json)
        if sampling.guided_regex is not None:
            pattern = sampling.guided_regex
        else:
            pattern = G.schema_to_regex(sampling.guided_json)
        ent = self._grammar_cache.get(key)
        if ent is None:
            dfa = G.compile_regex(
                pattern, max_states=self.config.max_grammar_states
            )
            if self._token_bytes is None:
                self._token_bytes = G.token_byte_images(
                    self.tokenizer, self.config.model.vocab_size
                )
            fsm = G.build_token_fsm(dfa, self._token_bytes)
            if not self._grammar_free:
                for k, e in list(self._grammar_cache.items()):
                    if e["refs"] == 0:  # evict a cold grammar's slot
                        self._grammar_free.append(e["slot"])
                        del self._grammar_cache[k]
                        del self._grammar_by_slot[e["slot"]]
                        break
            if not self._grammar_free:
                raise GrammarBankFull(
                    f"too many concurrent guided grammars "
                    f"(max {self.config.max_grammars})"
                )
            slot = self._grammar_free.pop()
            self.runner.register_grammar(slot, fsm)
            ent = {"slot": slot, "fsm": fsm, "refs": 0, "key": key}
            self._grammar_cache[key] = ent
            self._grammar_by_slot[slot] = ent
        ent["refs"] += 1
        return ent

    def grammar_slot_available(self, guided_regex=None,
                               guided_json=None) -> bool:
        """Advisory: could a request with this grammar be admitted now?

        Shares _grammar_key with _acquire_grammar so the two can never
        desynchronize. NOTE this is a check, not a reservation — real
        admission control is AsyncEngine.admit_batch, which runs the
        actual acquire atomically on the engine thread and surfaces
        GrammarBankFull before the server commits to a response."""
        key = _grammar_key(guided_regex, guided_json)
        if key in self._grammar_cache or self._grammar_free:
            return True
        return any(e["refs"] == 0 for e in self._grammar_cache.values())

    def _release_grammar(self, seq: Sequence) -> None:
        if seq.grammar_slot < 0:
            return
        ent = self._grammar_by_slot.get(seq.grammar_slot)
        if ent is not None and ent["refs"] > 0:
            ent["refs"] -= 1
        seq.grammar_slot = -1

    def has_unfinished(self) -> bool:
        # a decode dispatch in flight is unfinished work even where every
        # sequence in it has been aborted since
        return self.scheduler.has_work() or self._pending_decode is not None

    def live_request_ids(self) -> list[str]:
        """Request ids with scheduler state (waiting or running); aborting
        each one releases its KV blocks."""
        return self.scheduler.live_request_ids()

    # -- the step ------------------------------------------------------------
    def step(self) -> list[RequestOutput]:
        if self.clock.in_step:  # the async worker opened it
            return self._step()
        self.clock.begin_step()
        try:
            return self._step()
        finally:
            self.clock.end_step()

    def _step(self) -> list[RequestOutput]:
        # (begin_step opened the `schedule` phase)
        # land finished warm-tier fetches first so their sequences become
        # schedulable in THIS step's decision
        self._poll_prefetches()
        self.scheduler.step_num = self.clock.step_num
        out = self.scheduler.schedule()
        if out.is_empty:
            outputs = self._resolve_pending_ragged()
            outputs.extend(self._resolve_pending_decode())
            if (not outputs and self._prefetcher is not None
                    and self._prefetcher.jobs):
                # nothing else runnable and fetches in flight: a bounded
                # wait trades a busy-spin for latency no request observes.
                # Time spent here is the NON-overlapped share of prefetch
                # (``stats()`` reports it as the prefetch-overlap fraction).
                t0 = self.clock.enter("prefetch_wait")
                self._prefetcher.wait_any(0.002)
                self.prefetch_stall_seconds += (
                    self.clock.enter("postprocess") - t0)
            return outputs
        if out.prefills:
            # prefill chunks and decode rows share ONE packed dispatch
            return self._run_ragged(out)
        # decode consumes the first sampled token: the deferred ragged
        # step must land before decode inputs are built — and resolving
        # may FINISH sequences (max_tokens=1) the scheduler already put in
        # this step's decode batch
        ragged_landed = self._pending_ragged is not None
        outputs = self._resolve_pending_ragged()
        if (ragged_landed and self.arrival_probe is not None
                and self.arrival_probe()):
            # a request reached the intake while the ragged step ran. No
            # decode program is queued in front of it: the worker takes
            # it in and the next step is a ragged one with its prompt and
            # every decode row (nothing schedule() handed out for this
            # step has reached the sequences, as where `_arrival_first`
            # drops a prepared step). A request waiting in the scheduler
            # is no reason here: schedule() has just left it waiting
            self.ragged_landing_arrivals += 1
            return outputs
        if self._spec is not None:
            # drafts are proposed from whole token histories
            outputs.extend(self._resolve_pending_decode())
        decodes = [s for s in out.decodes
                   if s.status is SequenceStatus.RUNNING]
        if decodes:
            self.clock.enter("build")
            if self._spec is not None and self._propose_spec_drafts(decodes):
                # drafts ride the packed stream as prefill-shaped spans;
                # verification is fused in the same ragged dispatch
                outputs.extend(self._run_ragged(out, proposed=True))
            else:
                self._run_decode(decodes, outputs)
        else:
            outputs.extend(self._resolve_pending_decode())
        return outputs

    # -- speculative decoding (engine/spec.py) -------------------------------
    @staticmethod
    def _spec_seq_eligible(seq: Sequence) -> bool:
        """Per-sequence: speculation verifies against the raw-logits
        argmax, so only greedy rows with plain logits are eligible —
        sampled/penalised/controlled/grammar/logprobs rows decode
        normally in the SAME dispatch."""
        return (
            seq.sampling.temperature <= 0.0
            and not seq.sampling.presence_penalty
            and not seq.sampling.frequency_penalty
            and seq.token_ctrl is None
            and seq.sampling.logprobs is None  # verify emits argmax only
            and seq.grammar_slot < 0  # verify has no FSM mask
        )

    def _spec_grant_fn(self, seq: Sequence) -> int:
        """Scheduler hook: draft width to charge against the stream budget
        for this decode row (0 = ineligible or EWMA-cold)."""
        if not self._spec_seq_eligible(seq):
            return 0
        bound = min(
            seq.num_prompt_tokens + seq.sampling.max_tokens,
            self.config.model.max_model_len,
        )
        # drafting past the completion bound can never emit tokens
        return min(self._spec.grant(seq),
                   max(bound - 1 - seq.num_computed_tokens, 0))

    def _propose_spec_drafts(self, decodes: list[Sequence]) -> bool:
        """Consume each row's scheduler grant into actual drafts (n-gram
        prompt lookup over the NOW-complete token history — pendings must
        be resolved first). Returns True if any row has drafts; a granted
        row with no match decays its EWMA (the reserved budget was
        wasted) so cold sequences stop being charged."""
        from production_stack_tpu.engine.spec import propose_ngram

        sched = self.config.scheduler
        any_drafts = False
        for seq in decodes:
            k, seq.spec_grant = seq.spec_grant, 0  # consumed
            seq.spec_drafts = []
            if k <= 0:
                continue
            drafts = propose_ngram(
                seq.token_ids, k, sched.spec_ngram_max,
                sched.spec_ngram_min, sched.spec_window,
            )
            if drafts:
                seq.spec_drafts = drafts
                any_drafts = True
            else:
                self._spec.update(seq, k, 0)
        return any_drafts

    def _fetch(self, result_dev, kind: str) -> tuple:
        """Block on the results of a dispatch of ``kind``: the step
        clock's `wait` phase. Returns (the results on the host, the
        seconds blocked)."""
        t0 = self.clock.wait(kind)
        fetched = jax.device_get(result_dev)
        return fetched, self.clock.enter("postprocess") - t0

    # -- tiered KV (HBM ↔ host ↔ remote; see engine/kv_offload.py) -----------
    def _wire_tier_hooks(self) -> None:
        """Point the allocator's eviction at host demotion and the host
        store's eviction at remote demotion. Re-run after anything that
        rebuilds the allocator (sleep_mode)."""
        if self.host_kv is not None:
            self.scheduler.allocator.evict_hook = self._demote_evicted_block
            if self.remote_kv is not None:
                self.host_kv.demote_hook = self._demote_to_remote

    def _demote_evicted_block(self, block_id: int, chain_hash: int) -> None:
        """Allocator evict hook: an HBM block is about to be recycled —
        copy its slab down to host DRAM so the prefix survives the pool.
        Runs on the engine thread while the block's KV is still intact
        (before the id returns to the free list)."""
        if chain_hash in self.host_kv:
            return  # already resident (e.g. offloaded at finish)
        data = np.asarray(self.runner.export_blocks([block_id]))
        slab = np.ascontiguousarray(data[:, 0])  # (L, bs, 2KH, D)
        if self.host_kv.put(chain_hash, slab):
            self.hbm_demotions += 1
            self.tier_bytes[("host", "out")] += slab.nbytes

    def _demote_to_remote(self, chain_hash: int, slab) -> None:
        """Host-store demote hook: a host-LRU-evicted slab moves onward to
        the shared remote tier (bounded fire-and-forget — RemoteKVClient
        drops past its pending-put cap rather than grow a backlog)."""
        self.remote_kv.put_slab(chain_hash, slab)
        self.tier_bytes[("remote", "out")] += slab.nbytes

    def _start_tier_prefetch(self, seq: Sequence) -> None:
        """Admission hook: start the async warm-tier prefix lookup and park
        the sequence in PREFETCHING until the fetch lands (committed at the
        top of a later step). The old synchronous import stalled the whole
        serving loop for up to the remote timeout per admission; now a cold
        tier delays only this sequence's own prefill."""
        if self.prefetch_paused:
            self.prefetch_shed_count += 1
            return
        if self._prefetcher.submit(seq) is not None:
            seq.status = SequenceStatus.PREFETCHING

    def _poll_prefetches(self) -> None:
        if self._prefetcher is None:
            return
        for job in self._prefetcher.pop_done():
            self._commit_prefetch(job)

    def _commit_prefetch(self, job) -> None:
        """Land one finished prefetch: import the staged slabs into the
        blocks reserved at admission (block-table indirection only — the
        ragged dispatch never sees tier state) and release the sequence to
        PREFILLING. A sequence aborted mid-flight was already released (its
        blocks may belong to someone else), so staged data is only imported
        after re-checking the sequence still owns the snapshotted blocks."""
        try:
            slabs, host_n, remote_n = job.future.result()
        except Exception:  # tier lookup died: treat as a clean miss
            slabs, host_n, remote_n = [], 0, 0
        self._observe_prefetch(time.monotonic() - job.submit_time)
        seq = self.scheduler.seqs.get(job.request_id)
        if (seq is None or seq.status is not SequenceStatus.PREFETCHING
                or tuple(seq.block_ids[:len(job.block_snapshot)])
                != job.block_snapshot):
            self._prefetcher.dropped += 1
            if seq is not None and seq.status is SequenceStatus.PREFETCHING:
                seq.status = SequenceStatus.PREFILLING
            return
        seq.status = SequenceStatus.PREFILLING
        n = len(slabs)
        if not n:
            return  # warm-tier miss: the normal prefill recomputes
        bs = self.config.cache.block_size
        start = job.start_block
        target = seq.block_ids[start : start + n]
        data = np.stack(slabs).transpose(1, 0, 2, 3, 4)  # (L, n, bs, ...)
        self.runner.import_blocks(target, data)
        seq.num_computed_tokens += n * bs
        seq.num_cached_tokens += n * bs
        self.scheduler.allocator.commit_full_blocks(
            seq.token_ids[: seq.num_computed_tokens],
            seq.block_ids[: start + n],
        )
        self._prefetcher.committed += 1
        self.prefetch_blocks += n
        if host_n:
            self.tier_bytes[("host", "in")] += sum(
                s.nbytes for s in slabs[:host_n])
        if remote_n:
            self.tier_bytes[("remote", "in")] += sum(
                s.nbytes for s in slabs[host_n:])

    def _observe_prefetch(self, seconds: float) -> None:
        self.prefetch_count += 1
        self.prefetch_seconds_sum += seconds
        for i, edge in enumerate(self._PREFETCH_BUCKETS):
            if seconds <= edge:
                self.prefetch_hist[i] += 1
                return
        self.prefetch_hist[-1] += 1  # +Inf bucket

    def _host_offload_finished(self, seq: Sequence) -> None:
        """Copy a finishing sequence's full blocks to the warm tiers."""
        from production_stack_tpu.engine.kv_offload import chain_hashes

        bs = self.config.cache.block_size
        # only positions < num_computed hold valid KV (see Scheduler.finish)
        n_valid = min(len(seq.token_ids), seq.num_computed_tokens)
        n_full = min(n_valid // bs, len(seq.block_ids))
        if n_full <= 0:
            return
        import numpy as np

        data = self.runner.export_blocks(seq.block_ids[:n_full])
        slabs = np.ascontiguousarray(data.transpose(1, 0, 2, 3, 4))
        if self.host_kv is not None:
            added = self.host_kv.put_sequence(
                seq.token_ids[: n_full * bs], slabs)
            if added:
                self.tier_bytes[("host", "out")] += added * slabs[0].nbytes
        if self.remote_kv is not None:
            for h, slab in zip(
                chain_hashes(seq.token_ids[: n_full * bs], bs), slabs
            ):
                self.remote_kv.put_slab(h, slab)
                self.tier_bytes[("remote", "out")] += slab.nbytes

    # -- tenant attribution (observe-only; production_stack_tpu/tenancy.py) --
    def _tenant_map(self, entries) -> Optional[dict]:
        """Per-tenant token shares of one dispatch, from ``(seq, phase,
        goodput_tokens, live_tokens)`` rows: goodput feeds the per-tenant
        phase counters, live tokens weight the chip-second split. None
        when metering is off — the record_* calls then skip attribution
        entirely (bit-identical fleet totals either way)."""
        if self.perf is None or not self.perf.tenant_metering:
            return None
        tmap: dict = {}
        for seq, phase, goodput, live in entries:
            rec = tmap.setdefault(
                seq.tenant, {"prefill": 0, "decode": 0, "live": 0})
            rec[phase] += goodput
            rec["live"] += live
        return tmap

    def _attribute_seq_seconds(self, seconds: float, entries) -> None:
        """Ledger-grade per-sequence split of one dispatch's wall time by
        the same live-token weights as the tenant-level split — a
        sequence's accumulated ``chip_seconds`` lands in its usage-ledger
        record at finish."""
        if (self.perf is None or not self.perf.tenant_metering
                or seconds <= 0 or not entries):
            return
        shares = split_shares(
            seconds, {seq.request_id: live for seq, _, _, live in entries})
        for seq, _, _, _ in entries:
            seq.chip_seconds += shares.get(seq.request_id, 0.0)

    # -- the ragged step ----------------------------------------------------
    def _run_ragged(self, out, proposed: bool = False) -> list[RequestOutput]:
        """ONE dispatch for a mixed step: every decode row contributes one
        token (or a 1 + drafts speculative span), FCFS prefill chunks fill
        the rest of the token budget, packed in slot order into a single
        (1, W) stream. W is the narrowest of the scheduler's
        ``ragged_stream_widths`` that holds the tokens packed (the budget
        is the last of them): the step is as wide as what it carries, one
        steady-state compile signature a width, verify included, and what
        the scheduler decided is not looked at again. Draft-free
        decode-only steps still take _run_decode (multi-step fusion,
        inputs prepared under the step before)."""
        bs = self.config.cache.block_size
        outputs = self._resolve_pending_ragged()
        outputs.extend(self._resolve_pending_decode())
        decodes = [s for s in out.decodes
                   if s.status is SequenceStatus.RUNNING]
        prefills = [sp for sp in out.prefills
                    if not sp.seq.status.is_finished]
        if not decodes and not prefills:
            return outputs
        self.clock.enter("build")
        if self._spec is not None and not proposed:
            # pendings are resolved: token histories are complete, so the
            # scheduler's budget grants can become concrete drafts now
            self._propose_spec_drafts(decodes)
        B = self.config.scheduler.max_num_seqs
        T = self.config.scheduler.max_num_batched_tokens
        rows: dict[int, tuple] = {s.slot: ("d", s) for s in decodes}
        for sp in prefills:
            rows[sp.seq.slot] = ("p", sp)

        self._r_tokens[:] = 0
        self._r_positions[:] = -1
        self._r_slot_mapping[:] = -1
        if self.window:
            self._r_window_slot_mapping[:] = -1
        self._r_adapter_ids[:] = 0
        self._r_last_idx[:] = 0
        self._r_sample_mask[:] = 0.0
        self._context_lens[:] = 0
        self._presence[:] = 0.0
        self._frequency[:] = 0.0
        self._g_ids[:] = -1
        self._g_states[:] = 0
        self._ctrl_ids[:] = -1
        self._ctrl_vals[:] = 0.0
        self._ctrl_mode[:] = 0
        if self._spec is not None:
            # index 0 always points at a live stream token, so the fused
            # verify computes harmless argmaxes for draft-free rows
            self._r_verify_idx[:] = 0

        cu = 0
        seqs_in_step: list[Sequence] = []
        spec_rows: list[tuple[int, Sequence, list[int]]] = []
        p_tokens = p_ctx = p_rows = d_ctx = 0
        sp_tokens = sp_ctx = 0
        # (seq, phase, goodput, live) per packed row: the tenant
        # attribution shares of this fused dispatch (draft tokens carry
        # live weight but no goodput — they only become goodput if
        # accepted, via record_spec_accepted)
        t_entries: list[tuple] = []
        for slot in range(B):
            ent = rows.get(slot)
            if ent is None:
                self._r_cu[slot + 1] = cu
                continue
            kind, obj = ent
            if kind == "d":
                seq = obj
                pos = seq.num_computed_tokens  # index of the incoming token
                drafts = seq.spec_drafts if self._spec is not None else []
                n = 1 + len(drafts)
                self._r_tokens[0, cu : cu + n] = [seq.token_ids[pos]] + drafts
                self._r_positions[0, cu : cu + n] = np.arange(pos, pos + n)
                self._r_slot_mapping[cu : cu + n] = slot_mapping_for(
                    seq.block_ids, pos, n, bs
                )
                if self.window:
                    self._r_window_slot_mapping[cu : cu + n] = (
                        slot_mapping_for(seq.window_block_ids, pos, n, bs))
                self._r_adapter_ids[cu : cu + n] = seq.adapter_slot
                self._context_lens[slot] = pos + n
                self._steps[slot] = pos - seq.num_prompt_tokens + 1
                self._r_sample_mask[slot] = 1.0
                s = seq.sampling
                self._presence[slot] = s.presence_penalty
                self._frequency[slot] = s.frequency_penalty
                self._g_ids[slot] = seq.grammar_slot
                self._g_states[slot] = max(seq.fsm_state, 0)
                if drafts:
                    # the span's j-th token predicts position pos+j+1: the
                    # verify columns cover the drafts, the span's LAST
                    # token is last_idx — the normal sampling path provides
                    # the bonus token
                    self._r_verify_idx[slot, : len(drafts)] = np.arange(
                        cu, cu + len(drafts)
                    )
                    spec_rows.append((slot, seq, list(drafts)))
                    sp_tokens += len(drafts)
                    sp_ctx += pos + n
                cu += n
                d_ctx += pos + 1
                t_entries.append((seq, "decode", 1, n))
            else:
                sp = obj
                seq = sp.seq
                n = sp.chunk_len
                self._r_tokens[0, cu : cu + n] = seq.token_ids[
                    sp.chunk_start : sp.chunk_start + n
                ]
                self._r_positions[0, cu : cu + n] = np.arange(
                    sp.chunk_start, sp.chunk_start + n
                )
                self._r_slot_mapping[cu : cu + n] = slot_mapping_for(
                    seq.block_ids, sp.chunk_start, n, bs
                )
                if self.window:
                    self._r_window_slot_mapping[cu : cu + n] = (
                        slot_mapping_for(seq.window_block_ids,
                                         sp.chunk_start, n, bs))
                self._r_adapter_ids[cu : cu + n] = seq.adapter_slot
                self._context_lens[slot] = sp.chunk_start + n
                self._steps[slot] = 0
                completing = sp.chunk_start + n >= seq.prefill_target
                if completing and not seq.output_token_ids:
                    self._r_sample_mask[slot] = 1.0
                # the grammar constrains the FIRST sampled token only when
                # this chunk completes the prompt (state 0)
                if completing and seq.grammar_slot >= 0:
                    self._g_ids[slot] = seq.grammar_slot
                    self._g_states[slot] = 0
                s = seq.sampling
                cu += n
                p_tokens += n
                p_ctx += sp.chunk_start + n
                p_rows += 1
                t_entries.append((seq, "prefill", n, n))
            nb = len(seq.block_ids)
            self._block_tables[slot, :nb] = seq.block_ids
            if self.window:
                self._window_tables[slot, :len(seq.window_block_ids)] = (
                    seq.window_block_ids)
            self._r_last_idx[slot] = cu - 1
            self._temps[slot] = s.temperature
            self._top_ps[slot] = s.top_p
            self._top_ks[slot] = s.top_k
            self._seeds[slot] = s.seed or 0
            if seq.token_ctrl is not None:
                (self._ctrl_ids[slot], self._ctrl_vals[slot],
                 self._ctrl_mode[slot]) = seq.token_ctrl
            self._r_cu[slot + 1] = cu
            seqs_in_step.append(seq)
        assert cu <= T, f"packed {cu} tokens over budget {T}"
        # the rows past W are padding the packing above left as it found
        # them: the program runs the same computation on fewer of them
        W = self.config.scheduler.stream_width_for(cu)

        greedy_only = all(
            s.sampling.temperature <= 0.0 for s in seqs_in_step
        )
        use_lora = any(s.adapter_slot for s in seqs_in_step)
        # prefill rows never penalize their first sample; penalties gate
        # on the decode rows only
        use_penalties = any(
            s.sampling.presence_penalty or s.sampling.frequency_penalty
            for s in decodes
        )
        if use_penalties and self._count_reset_slots:
            for seq in self._count_reset_slots:
                if seq.slot >= 0:
                    self.runner.set_count_row(seq.slot, seq.output_token_ids)
            self._count_reset_slots.clear()
        use_controls = any(s.token_ctrl is not None for s in seqs_in_step)
        use_grammar = bool((self._g_ids >= 0).any())
        self.clock.describe("ragged", rows=len(seqs_in_step), tokens=cu,
                            width=W)
        t_call = self.clock.enter("snapshot")
        result_dev = self.runner.ragged_step(
            self._r_tokens[:, :W], self._r_positions[:, :W],
            self._block_tables, self._context_lens, self._r_cu,
            self._r_slot_mapping[:W],
            self._r_last_idx, self._r_sample_mask,
            self._temps, self._top_ps, self._top_ks, self._seeds,
            self._steps,
            greedy_only=greedy_only,
            presence=self._presence if use_penalties else None,
            frequency=self._frequency if use_penalties else None,
            adapter_ids=self._r_adapter_ids[:W] if use_lora else None,
            ctrl=((self._ctrl_ids, self._ctrl_vals, self._ctrl_mode)
                  if use_controls else None),
            g_ids=self._g_ids if use_grammar else None,
            g_states=self._g_states if use_grammar else None,
            verify_idx=(self._r_verify_idx
                        if self._spec is not None else None),
            fetch=False,
            **({"window": (self._window_tables,
                           self._r_window_slot_mapping[:W])}
               if self.window else {}),
        )
        dispatch_s = self.clock.enter("postprocess") - t_call
        for sp in prefills:
            self._note_prompt_dispatch(sp.seq)
        if self.perf is not None:
            # draft/verify spans are prefill-shaped work with zero goodput;
            # accepted tokens land as decode goodput at resolve time
            self.perf.record_ragged(p_tokens, p_ctx, p_rows,
                                    len(decodes), d_ctx,
                                    spec_tokens=sp_tokens, spec_ctx=sp_ctx,
                                    spec_rows=len(spec_rows),
                                    seconds=dispatch_s,
                                    tenants=self._tenant_map(t_entries))
            self._attribute_seq_seconds(dispatch_s, t_entries)
        self.ragged_dispatches += 1
        self.ragged_narrow_dispatches += W < T
        self.ragged_live_tokens += cu
        if self.recurrent is not None:
            q_len = np.diff(self._r_cu)
            self.recurrent.record_ragged(
                q_len, continues_one_row(q_len, self._context_lens),
                sum(sp.chunk_start == 0 for sp in prefills))
        if self.latent is not None:
            # another kernel: the ragged kernel's walks and windows stay 0
            self.latent.record("ragged", np.diff(self._r_cu),
                               self._context_lens)
        else:
            group = self.config.model.q_per_kv
            # the kernel's own tile (it shrinks past 16 KV heads)
            tile = q_tile_for(group, self.config.model.cache_kv_heads)
            walks, narrow = count_walks(self._r_cu, W, group, q_tile=tile)
            self.ragged_attn_walks += walks
            self.ragged_attn_narrow_walks += narrow
            windows, interior = count_windows(
                self._r_cu, self._context_lens, W, group,
                self.config.cache.block_size, q_tile=tile)
            if self.window_counters is not None:
                self.window_counters.record_ragged(
                    windows, count_windows(
                        self._r_cu, self._context_lens, W, group,
                        self.config.cache.block_size, q_tile=tile,
                        window=self.window)[0],
                    np.diff(self._r_cu), self._context_lens)
            self.ragged_attn_windows += windows
            self.ragged_attn_interior_windows += interior

        # scheduler-visible state advances NOW; results land next step
        # (same deferral contract as a decode dispatch). A spec
        # row advances only its guaranteed token here — position pos holds
        # the last ACCEPTED token's KV regardless of draft outcome; the
        # accepted-draft advance happens at resolve, which for spec steps
        # is synchronous below.
        spec_slots = {slot for slot, _, _ in spec_rows}
        decode_rows = []
        for seq in decodes:
            seq.num_computed_tokens += 1
            if seq.slot not in spec_slots:
                decode_rows.append((seq.slot, seq))
        prefill_rows = []
        for sp in prefills:
            seq = sp.seq
            seq.num_computed_tokens = sp.chunk_start + sp.chunk_len
            if not seq.prefill_done:
                continue  # more chunks to go
            seq.status = SequenceStatus.RUNNING
            self._slot_seq[seq.slot] = seq
            s = seq.sampling
            if s.presence_penalty or s.frequency_penalty:
                self._count_reset_slots.append(seq)
            if seq.output_token_ids:
                # preemption-recompute: context rebuilt, newest token still
                # the pending decode input — nothing sampled this step
                continue
            prefill_rows.append((seq.slot, seq))
        self._pending_ragged = {
            "prefill_rows": prefill_rows,
            "decode_rows": decode_rows,
            "spec_rows": spec_rows,
            "result": result_dev,
            "tenant_entries": t_entries,
        }
        if spec_rows:
            # acceptance decides how far each spec row really advanced —
            # the scheduler must see that before its next decision, so
            # verify-bearing dispatches resolve synchronously (the draft
            # speedup dwarfs the lost one-step overlap)
            outputs.extend(self._resolve_pending_ragged())
        return outputs

    def _resolve_pending_ragged(self) -> list[RequestOutput]:
        if self._pending_ragged is None:
            return []
        pending = self._pending_ragged
        self._pending_ragged = None
        fetched, fetch_s = self._fetch(pending["result"], "ragged")
        fetched = self.runner.take_counters(
            tuple(np.asarray(x) for x in fetched))
        if self.perf is not None:
            # the blocking result fetch is dispatch wall time too — billed
            # by the same live-token shares so conservation spans the
            # dispatch/resolve split
            entries = pending.get("tenant_entries") or []
            tmap = self._tenant_map(entries)
            if tmap:
                self.perf.attribute_seconds(
                    {t: rec["live"] for t, rec in tmap.items()}, fetch_s)
            self._attribute_seq_seconds(fetch_s, entries)
        return self._finish_ragged(pending, fetched)

    def _finish_ragged(self, pending, fetched) -> list[RequestOutput]:
        """Append one sampled token per resolved row: first tokens for the
        prompts that completed in that dispatch, next tokens for its decode
        rows (num_computed already advanced at dispatch) — and for spec
        rows, the longest model-confirmed draft prefix plus the bonus
        token, with rejected-draft KV rolled back exactly by NOT advancing
        num_computed past the accepted prefix (Scheduler.finish commits
        only positions below it; the garbage slots are rewritten when the
        real tokens for those positions are dispatched)."""
        sampled = fetched[0]
        if self._spec is not None:
            verify, lp = fetched[1], fetched[2:] or None
        else:
            verify, lp = None, (fetched[1:] if len(fetched) > 1 else None)
        live, token_lists, lp_lists = [], [], []
        for slot, seq, drafts in pending.get("spec_rows", ()):
            if seq.status.is_finished:
                continue  # aborted while the dispatch was in flight
            d = len(drafts)
            verified = [int(verify[slot, j]) for j in range(d)]
            verified.append(int(sampled[slot]))  # span's last_idx = bonus
            from production_stack_tpu.engine.spec import accept_drafts

            new_tokens, n_acc = accept_drafts(drafts, np.asarray(verified))
            self._spec.update(seq, d, n_acc)
            self.spec_drafted += d
            self.spec_accepted += n_acc
            self.spec_steps += 1
            new_toks = []
            for j, t in enumerate(new_tokens):
                if j:
                    # position pos+j's KV (input: accepted draft j-1) just
                    # became valid; the dispatch advanced position pos only
                    seq.num_computed_tokens += 1
                seq.output_token_ids.append(t)
                new_toks.append(t)
                self.total_output_tokens += 1
                if seq.first_token_time is None:
                    self._stamp_first_token(seq)
                if self._check_stop(seq, t) is not None:
                    break
            self.spec_step_tokens += len(new_toks)
            if self.perf is not None and len(new_toks) > 1:
                # the guaranteed token was already counted as decode
                # goodput at dispatch; accepted drafts land here
                self.perf.record_spec_accepted(len(new_toks) - 1,
                                               tenant=seq.tenant)
            live.append(seq)
            token_lists.append(new_toks)
            lp_lists.append(None)  # spec rows never request logprobs
        for slot, seq in pending["prefill_rows"]:
            if seq.status.is_finished:
                continue  # aborted while the dispatch was in flight
            token = int(sampled[slot])
            self._stamp_first_token(seq)
            seq.output_token_ids.append(token)
            if seq.grammar_slot >= 0 and seq.fsm is not None:
                seq.fsm_state = int(seq.fsm.trans[0, token])
            self.total_output_tokens += 1
            live.append(seq)
            token_lists.append([token])
            lp_lists.append(
                [_lp_row(lp, slot)]
                if lp is not None and seq.sampling.logprobs is not None
                else None
            )
        for slot, seq in pending["decode_rows"]:
            if seq.status.is_finished:
                continue
            t = int(sampled[slot])
            seq.output_token_ids.append(t)
            if seq.grammar_slot >= 0 and seq.fsm is not None:
                if 0 <= t < seq.fsm.trans.shape[1]:
                    seq.fsm_state = int(
                        seq.fsm.trans[max(seq.fsm_state, 0), t]
                    )
            self.total_output_tokens += 1
            live.append(seq)
            token_lists.append([t])
            lp_lists.append(
                [_lp_row(lp, slot)]
                if lp is not None and seq.sampling.logprobs is not None
                else None
            )
        return self._postprocess(live, token_lists, lp_lists)

    def _run_decode(self, decodes: list[Sequence],
                    outputs: list[RequestOutput]) -> None:
        """One decode dispatch over ``decodes``, launched and not waited
        for: its tokens land in the step after. That step schedules,
        builds, packs and commits its own inputs while this dispatch
        runs, and launches from the ``next_tok`` this one leaves on the
        device, a future of the program in flight: the device orders the
        two. No launch joins the device's queue without the arrival probe
        having been asked. Where the landing is foreseen and nothing
        speaks against it the launch is made a lead before the landing
        (`_launch_ahead`), so that the device goes from one program to
        the next without the host; otherwise at the landing, once
        `_arrival_first` has let no ragged step go first. At most one
        program ever stands queued behind the one in flight: a launch
        ahead is followed by the wait for the landing it went ahead of.
        A dispatch in flight that cannot feed this one is resolved before
        the build, and the tokens come from the host, in order: its
        results are wanted there (log-probabilities, the state of a
        grammar), or a member was not in it, slot for slot. ``outputs``
        collects what the step resolves."""
        bs = self.config.cache.block_size
        use_logprobs = any(s.sampling.logprobs is not None for s in decodes)
        use_grammar = any(s.grammar_slot >= 0 for s in decodes)
        pending = self._pending_decode
        if pending is not None and (
                use_logprobs or use_grammar  # the host mirrors the FSM state
                or any(pending["rows"].get(s.slot) is not s
                       for s in decodes)):
            outputs.extend(self._resolve_pending_decode())
            decodes = [s for s in decodes
                       if s.status is SequenceStatus.RUNNING]
            if not decodes:
                return
            pending = None
        self.clock.enter("build")
        self._context_lens[:] = 0
        self._slot_mapping[:] = -1
        if self.window:
            self._window_slot_mapping[:] = -1
        for seq in decodes:
            i = seq.slot
            pos = seq.num_computed_tokens  # index of the incoming token
            if pending is None:
                self._tokens[i] = seq.token_ids[pos]
            self._positions[i] = pos
            n = len(seq.block_ids)
            self._block_tables[i, :n] = seq.block_ids
            self._context_lens[i] = pos + 1
            self._slot_mapping[i] = seq.block_ids[pos // bs] * bs + pos % bs
            if self.window:
                self._window_tables[i, :len(seq.window_block_ids)] = (
                    seq.window_block_ids)
                self._window_slot_mapping[i] = (
                    seq.window_block_ids[pos // bs] * bs + pos % bs)
            s = seq.sampling
            self._temps[i] = s.temperature
            self._top_ps[i] = s.top_p
            self._top_ks[i] = s.top_k
            self._seeds[i] = s.seed or 0
            # fold counter = tokens sampled so far; under deferral the
            # output list lags, so derive it from num_computed
            self._steps[i] = pos - seq.num_prompt_tokens + 1
            self._presence[i] = s.presence_penalty
            self._frequency[i] = s.frequency_penalty
            self._adapter_ids[i] = seq.adapter_slot
            if seq.token_ctrl is not None:
                (self._ctrl_ids[i], self._ctrl_vals[i],
                 self._ctrl_mode[i]) = seq.token_ctrl
            else:
                self._ctrl_ids[i] = -1
                self._ctrl_vals[i] = 0.0
                self._ctrl_mode[i] = 0
            self._g_ids[i] = seq.grammar_slot
            self._g_states[i] = max(seq.fsm_state, 0)

        # multi_step fused decode+sample iterations in one dispatch; sampled
        # tokens come back (K, B) and are appended until a stop fires
        greedy_only = all(s.sampling.temperature <= 0.0 for s in decodes)
        use_lora = any(s.adapter_slot for s in decodes)
        use_penalties = any(
            s.sampling.presence_penalty or s.sampling.frequency_penalty
            for s in decodes
        )
        if use_penalties and self._count_reset_slots:
            for seq in self._count_reset_slots:
                if seq.slot >= 0:
                    self.runner.set_count_row(seq.slot, seq.output_token_ids)
            self._count_reset_slots.clear()
        use_controls = any(s.token_ctrl is not None for s in decodes)
        K = max(self.config.scheduler.multi_step, 1)
        self.clock.describe("decode", rows=len(decodes),
                            tokens=K * len(decodes))
        t_call = self.clock.enter("snapshot")
        launch = self.runner.prepare_decode(
            self._tokens, self._positions, self._block_tables,
            self._context_lens, self._slot_mapping,
            self._temps, self._top_ps, self._top_ks, self._seeds, self._steps,
            greedy_only=greedy_only,
            presence=self._presence if use_penalties else None,
            frequency=self._frequency if use_penalties else None,
            adapter_ids=self._adapter_ids if use_lora else None,
            ctrl=((self._ctrl_ids, self._ctrl_vals, self._ctrl_mode)
                  if use_controls else None),
            tokens_dev=pending is not None,
            g_ids=self._g_ids if use_grammar else None,
            g_states=self._g_states if use_grammar else None,
            want_logprobs=use_logprobs,
            **({"window": (self._window_tables, self._window_slot_mapping)}
               if self.window else {}),
        )
        ahead = pending is not None and self._launch_ahead(
            pending, len(decodes))
        if pending is not None and not ahead:
            self._pending_decode = None
            self._fetch_decode(pending)  # the device's time ends here
            if self._arrival_first(pending, len(decodes)):
                # what was prepared is dropped, nothing of it has reached
                # the sequences: the next step() schedules the ragged step
                outputs.extend(self._finish_decode(pending))
                return
        if pending is not None:
            t_call = self.clock.now()  # its pack and commit are in the wait
        pend = {"rows": {s.slot: s for s in decodes},
                "ctx": int(self._context_lens.sum()), "wait_s": 0.0,
                "like": (len(decodes), greedy_only, use_penalties, use_lora,
                         use_controls)}
        pend["sampled"], pend["next_tok"], pend["counters"], *pend["lp"] = (
            launch(pending["next_tok"] if pending else None))
        pend["start"] = self.clock.enter("postprocess")
        pend["launch_s"] = pend["start"] - t_call
        self.decode_dispatches += 1
        self.decode_prepared_launches += pending is not None
        self.decode_ahead_launches += ahead
        if self.recurrent is not None:
            self.recurrent.record_decode(K)
        if self.window_counters is not None:
            self.window_counters.record_decode(self._context_lens, K)
        if self.latent is not None:
            self.latent.record("decode", self._context_lens > 0,
                               self._context_lens, iterations=K)
        # a cross-attention layer calls the kernel on another's cache layer
        attn_calls = K * (self.config.model.cache_layers
                          + self.config.model.count_layers("cross"))
        self.decode_attn_calls += attn_calls
        windowed = K * self.config.model.count_layers("swa")
        self.decode_attn_slab_calls += (
            (attn_calls - windowed)
            * self.runner.decode_attn_slab
            + windowed * self.runner.decode_attn_slab_windowed)
        # the scheduler's block growth needs the advance now; the tokens
        # are appended at the landing
        for seq in decodes:
            seq.num_computed_tokens += K
        self._pending_decode = pend
        if ahead:
            # the landing it went ahead of, with `pend` queued behind: a
            # row that stopped in `pending` has surplus tokens in `pend`,
            # dropped at its landing as an abort's are; blocks freed here
            # may be handed out at once, whatever writes them next is
            # queued behind the program that still touches them
            self._fetch_decode(pending)
            pend["start"] = self._queued_landing_t = pending["landed"]
            lead = pending["landed"] - t_call
            self.decode_ahead_lead_seconds += lead
            self.decode_ahead_lead_max_seconds = max(
                self.decode_ahead_lead_max_seconds, lead)
        if pending is not None:
            outputs.extend(self._finish_decode(pending))

    def _launch_ahead(self, pending, n_next: int) -> bool:
        """Asked with ``pending`` in flight and the next decode step
        prepared (``n_next`` rows, all of them ``pending``'s): may it be
        queued behind ``pending`` now? Only where nothing `_arrival_first`
        would need the landed tokens for can matter: no request waits in
        the scheduler, or the next step has every row of the one in
        flight and no token can stop one. Then the thread sleeps until a
        lead before the landing `_landing_expected` foresees and asks
        the probe: an empty intake lets the launch go. A request that
        reaches the intake from then to the landing waits the queued
        program out (`arrivals_behind_queued_decode`); what is queued
        cannot be taken back. Every runner takes the not yet landed
        ``next_tok`` (the mirrored one chains its followers' own copy),
        so none is held to the landing here."""
        if self.landing_wait is None:
            return False
        expected = self._landing_expected(pending)
        if expected is None:
            return False
        rows = pending["rows"]
        if self.scheduler.waiting and (
                n_next < len(rows) or self._stop_tokens(rows)):
            return False
        t0 = self.clock.wait("decode")
        self.landing_wait(expected - DECODE_AHEAD_LEAD_S)
        pending["wait_s"] += self.clock.now() - t0
        return self.arrival_probe is None or not self.arrival_probe()

    def _landing_expected(self, pending) -> Optional[float]:
        """The stamp at which ``pending`` should be seen to land: its
        start (its launch or, queued ahead, the landing before it) and as
        long as the shortest of the last few dispatches like it ran (the
        same rows and variant, so the same program over the same work but
        a token a row). The shortest, because a run can only read long: a
        dispatch that reached the device late ran from the landing before
        it through the device's idle time, and a landing foreseen from
        such a run makes the next launch late in turn. None where the
        dispatch landed last was of another kind: after a ragged step
        that changed the rows the first landing is waited for."""
        like, runs = self._decode_runs
        if like != pending["like"]:
            return None
        return pending["start"] + min(runs)

    def _stop_tokens(self, rows: dict) -> set:
        """The tokens that would stop one of ``rows`` ({slot: sequence})."""
        stops = {t for s in rows.values() for t in s.sampling.stop_token_ids}
        if (self.tokenizer.eos_id is not None
                and not all(s.sampling.ignore_eos for s in rows.values())):
            stops.add(self.tokenizer.eos_id)
        return stops

    def _arrival_first(self, pending, n_next: int) -> bool:
        """Asked at the landing of ``pending`` with the next decode step
        prepared (``n_next`` rows, all of them ``pending``'s) and not
        queued ahead: should a ragged step run before it? Where something has reached the intake
        queue, or a request waits in the scheduler for a slot or for
        blocks and this landing frees some: a row gone from the next step
        (a completion bound reached, which the scheduler knew; an abort),
        or a landed token that stops its sequence, found by one compare
        over all of them. A queue that nothing frees stops no launch."""
        if self.arrival_probe is not None and self.arrival_probe():
            return True
        if not self.scheduler.waiting:
            return False
        rows = pending["rows"]
        if n_next < len(rows):
            return True
        return bool(np.isin(pending["sampled"][:, list(rows)],
                            list(self._stop_tokens(rows))).any())

    def _resolve_pending_decode(self) -> list[RequestOutput]:
        if self._pending_decode is None:
            return []
        pending = self._pending_decode
        self._pending_decode = None
        self._fetch_decode(pending)
        return self._finish_decode(pending)

    def _fetch_decode(self, pending) -> None:
        """Block on a launched decode dispatch and put its results into
        ``pending`` in place of the device arrays (sampled tokens (K, B),
        the log-probability arrays where the variant returns them, what
        an MoE model or a looped stack counted), with the seconds
        blocked, the stamp of the landing and, for `_landing_expected`,
        how long the dispatch ran."""
        (sampled, pending["counters"], *lp), wait_s = self._fetch(
            (pending["sampled"], pending["counters"], *pending["lp"]),
            "decode")
        pending["wait_s"] += wait_s  # to what `_launch_ahead` slept of it
        pending["landed"] = self.clock.now()
        if self._decode_runs[0] != pending["like"]:
            self._decode_runs = (pending["like"],
                                 deque(maxlen=DECODE_RUNS_KEPT))
        self._decode_runs[1].append(pending["landed"] - pending["start"])
        pending["sampled"] = np.asarray(sampled)
        pending["lp"] = [np.asarray(x) for x in lp]

    def _finish_decode(self, pending) -> list[RequestOutput]:
        """Charge, append and stop-check one decode dispatch's sampled
        tokens, on the host by now (`_fetch_decode`); ``num_computed``
        moved at its launch. What it is charged is its own launch and its
        own wait."""
        sampled = pending["sampled"]
        # [tok_lp (K, B), ids (K, B, N), lps (K, B, N)], or nothing
        lp = pending["lp"]
        if pending["counters"] is not None:
            self.runner.record_counters("decode", pending["counters"])
        if self.perf is not None:
            K, seconds = len(sampled), pending["launch_s"] + pending["wait_s"]
            entries = [(seq, "decode", K, K)
                       for seq in pending["rows"].values()]
            self.perf.record_decode(
                len(entries), K, pending["ctx"],
                seconds=seconds, tenants=self._tenant_map(entries),
            )
            self._attribute_seq_seconds(seconds, entries)
        token_lists = []
        lp_lists = []
        live = []
        for slot, seq in pending["rows"].items():
            if seq.status.is_finished:
                continue  # aborted while in flight; surplus tokens dropped
            want_lp = bool(lp) and seq.sampling.logprobs is not None
            new_toks = []
            new_lps = [] if want_lp else None
            for k in range(sampled.shape[0]):
                t = int(sampled[k, slot])
                seq.output_token_ids.append(t)
                new_toks.append(t)
                if seq.grammar_slot >= 0 and seq.fsm is not None:
                    # mirror the device-side FSM advance (kept tokens only:
                    # stop-discarded surplus must not move the state)
                    if 0 <= t < seq.fsm.trans.shape[1]:
                        seq.fsm_state = int(
                            seq.fsm.trans[max(seq.fsm_state, 0), t]
                        )
                if want_lp:
                    new_lps.append(
                        _lp_row((lp[0][k], lp[1][k], lp[2][k]), slot)
                    )
                self.total_output_tokens += 1
                if self._check_stop(seq, t) is not None:
                    break
            live.append(seq)
            token_lists.append(new_toks)
            lp_lists.append(new_lps)
        return self._postprocess(live, token_lists, lp_lists)

    def _note_prompt_dispatch(self, seq: Sequence) -> None:
        """The dispatch just launched carried rows of ``seq``'s prompt:
        the first such launch is where its prefill starts, and until its
        first token each one counts (a prompt recomputed after a
        preemption has its first token behind it)."""
        if seq.output_token_ids:
            return
        if seq.first_launch_time is None:
            seq.first_launch_time = self.clock.launch_t
            seq.first_launch_step = self.clock.step_num
        seq.prefill_dispatches += 1

    def _stamp_first_token(self, seq: Sequence) -> None:
        seq.first_token_time = self.clock.now()
        seq.first_token_step = self.clock.step_num

    def _postprocess(
        self, seqs: list[Sequence], token_lists: list[list[int]],
        lp_lists: Optional[list] = None,
    ) -> list[RequestOutput]:
        outputs = []
        for j, (seq, toks) in enumerate(zip(seqs, token_lists)):
            status = self._check_stop(seq, toks[-1]) if toks else None
            if status is not None:
                if self.host_kv is not None or self.remote_kv is not None:
                    self._host_offload_finished(seq)
                self.scheduler.finish(seq, status)
                self._slot_seq.pop(seq.slot, None)
                self._release_grammar(seq)
                seq.finish_time = self.clock.now()
                seq.finish_step = self.clock.step_num
                if self.perf is not None and seq.admit_time is not None:
                    self.perf.note_request(
                        seq.tenant, seq.admit_time - seq.arrival_time)
            outputs.append(
                RequestOutput(
                    request_id=seq.request_id,
                    new_token_ids=list(toks),
                    finished=status is not None,
                    finish_reason=seq.finish_reason(),
                    num_prompt_tokens=seq.num_prompt_tokens,
                    num_output_tokens=len(seq.output_token_ids),
                    num_cached_tokens=seq.num_cached_tokens,
                    tenant=seq.tenant,
                    chip_seconds=seq.chip_seconds,
                    block_ids=(seq.released_block_ids if status is not None
                               else None),
                    arrival_time=(seq.arrival_time if status is not None
                                  else None),
                    admit_time=(seq.admit_time if status is not None
                                else None),
                    first_token_time=(seq.first_token_time
                                      if status is not None else None),
                    finish_time=(seq.finish_time if status is not None
                                 else None),
                    enqueue_time=(seq.enqueue_time if status is not None
                                  else None),
                    first_launch_time=(seq.first_launch_time
                                       if status is not None else None),
                    steps=({"enqueued": seq.enqueue_step,
                            "arrival": seq.arrival_step,
                            "admitted": seq.admit_step,
                            "first_launch": seq.first_launch_step,
                            "first_token": seq.first_token_step,
                            "last_token": seq.finish_step}
                           if status is not None else None),
                    arrival_after=(seq.arrival_after if status is not None
                                   else None),
                    prefill_dispatches=seq.prefill_dispatches,
                    new_logprobs=(lp_lists[j] if lp_lists is not None
                                  else None),
                )
            )
        return outputs

    # -- KV export/import (disaggregated prefill→decode; P-side blocks stay
    #    content-addressed after finish, D-side import = prefix injection) --
    def export_kv(self, block_ids: list[int]):
        return self.runner.export_blocks(block_ids)

    def import_kv(self, prompt_token_ids: list[int], data) -> int:
        """Write transferred blocks into the pool and register their content
        hashes so admission prefix-hits them. Returns tokens now cached.
        (Monolithic variant of the streamed begin/range/finish flow.)"""
        got = self.begin_kv_import(prompt_token_ids, int(data.shape[1]))
        if got is None:
            return 0
        local, n_full = got
        self.runner.import_blocks(local, data[:, :n_full])
        return self.finish_kv_import(prompt_token_ids, local)

    # -- streaming KV import (chunked layer-group transfer; see
    #    engine/kv_transfer.py for the overlap pipeline) --------------------
    def begin_kv_import(self, prompt_token_ids: list[int],
                        n_remote_blocks: int):
        """Reserve local blocks for an incoming streamed transfer. Returns
        (local_block_ids, n_full_blocks) or None if the pool is full."""
        bs = self.config.cache.block_size
        n_full = min(n_remote_blocks, (len(prompt_token_ids) - 1) // bs)
        if n_full <= 0:
            return None
        local = self.scheduler.allocator.take_free_blocks(n_full)
        if local is None:
            return None
        return local, n_full

    def import_kv_range(self, local_blocks: list[int], layer_lo: int,
                        data) -> None:
        self.runner.import_blocks_range(local_blocks, layer_lo, data)

    def finish_kv_import(self, prompt_token_ids: list[int],
                         local_blocks: list[int]) -> int:
        """Commit the streamed blocks as prefix-cache content."""
        bs = self.config.cache.block_size
        alloc = self.scheduler.allocator
        alloc.commit_full_blocks(
            prompt_token_ids[: len(local_blocks) * bs], local_blocks
        )
        alloc.free_blocks(local_blocks)  # refcount 0 → cached + matchable
        return len(local_blocks) * bs

    def abort_kv_import(self, local_blocks: list[int]) -> None:
        self.scheduler.allocator.free_blocks(local_blocks)

    # -- pushed transfers (decode role: POST /kv/recv lands frames here,
    #    then the request with the matching transfer_id splices in) --------
    def begin_kv_receive(self, n_blocks: int):
        """Reserve ``n_blocks`` fresh pool blocks for a pushed transfer —
        unlike ``begin_kv_import`` this takes the producer's FULL block
        list (the trailing partial block too): the blocks become a live
        sequence's table, not content-addressed cache, so the
        leave-one-token-uncached rule does not apply. Returns block ids
        or None when the pool can't cover it (producer falls back to
        leaving pull params)."""
        if n_blocks <= 0:
            return None
        return self.scheduler.allocator.take_free_blocks(n_blocks)

    def splice_request(
        self,
        request_id: str,
        prompt_token_ids: list[int],
        first_token: int,
        sampling: "SamplingParams",
        blocks: list[int],
        adapter_slot: int = 0,
        tenant: str = "anonymous",
        enqueued: Optional[tuple] = None,
    ) -> Sequence:
        """Engine-thread: turn a completed P→D transfer into a RUNNING
        decode row. The sequence enters with the prompt fully computed
        and the prefill-produced first token already in its output, so
        the ragged scheduler treats it as decode-ready — no re-prefill.
        ``sampling.max_tokens`` counts the WHOLE completion including the
        pre-loaded first token (``_check_stop`` compares against
        ``len(output_token_ids)``). On failure the caller still owns the
        blocks; on success the normal finish/abort paths release them."""
        if len(blocks) * self.config.cache.block_size < len(prompt_token_ids):
            raise ValueError("spliced blocks do not cover the prompt")
        sampling = sampling.clamped(
            self.config.model.max_model_len, len(prompt_token_ids)
        )
        if sampling.seed is None:
            sampling = dataclasses.replace(
                sampling, seed=int.from_bytes(os.urandom(4), "little"),
            )
        from production_stack_tpu.engine.sampling import make_token_controls

        seq = Sequence(request_id, list(prompt_token_ids), sampling,
                       adapter_slot=adapter_slot,
                       tenant=tenant or "anonymous",
                       token_ctrl=make_token_controls(
                           sampling, self.config.model.vocab_size))
        self._stamp_arrival(seq, enqueued)
        seq.output_token_ids = [int(first_token)]
        seq.num_computed_tokens = len(prompt_token_ids)
        seq.num_cached_tokens = len(prompt_token_ids)
        seq.block_ids = list(blocks)
        self.scheduler.splice(seq)
        self._slot_seq[seq.slot] = seq
        if sampling.presence_penalty or sampling.frequency_penalty:
            # the pre-loaded first token must count toward penalties just
            # as if this engine had prefilled it
            self._count_reset_slots.append(seq)
        self.total_prompt_tokens += len(prompt_token_ids)
        self.spliced_seqs += 1
        return seq

    def _check_stop(self, seq: Sequence, token: int) -> Optional[SequenceStatus]:
        s = seq.sampling
        if not s.ignore_eos and self.tokenizer.eos_id is not None and token == self.tokenizer.eos_id:
            return SequenceStatus.FINISHED_STOPPED
        if token in s.stop_token_ids:
            return SequenceStatus.FINISHED_STOPPED
        if len(seq.output_token_ids) >= s.max_tokens:
            return SequenceStatus.FINISHED_LENGTH
        if seq.num_tokens >= self.config.model.max_model_len:
            return SequenceStatus.FINISHED_LENGTH
        return None

    # -- metrics (the /metrics contract) -------------------------------------
    def stats(self) -> dict:
        alloc = self.scheduler.allocator
        out = {
            "num_requests_running": self.scheduler.num_running,
            "num_requests_waiting": self.scheduler.num_waiting,
            "gpu_cache_usage_perc": alloc.usage,
            "gpu_prefix_cache_hits_total": alloc.prefix_hits,
            "gpu_prefix_cache_queries_total": alloc.prefix_queries,
            "prompt_tokens_total": self.total_prompt_tokens,
            "generation_tokens_total": self.total_output_tokens,
            "cpu_cache_usage_perc": 0.0,
            "cpu_prefix_cache_hits_total": 0,
            "cpu_prefix_cache_queries_total": 0,
            "spec_decode_num_draft_tokens_total": self.spec_drafted,
            "spec_decode_num_accepted_tokens_total": self.spec_accepted,
            # cumulative acceptance ratio + mean tokens emitted per
            # verified span (1 guaranteed + accepted drafts); both 0 until
            # the first verify so dashboards read "off" as flatline
            "spec_decode_acceptance_rate": (
                self.spec_accepted / self.spec_drafted
                if self.spec_drafted else 0.0
            ),
            "spec_decode_tokens_per_step": (
                self.spec_step_tokens / self.spec_steps
                if self.spec_steps else 0.0
            ),
            "aborted_seqs_total": self.aborted_seqs,
            "spliced_seqs_total": self.spliced_seqs,
            "num_preemptions_total": self.scheduler.preemptions,
            # per-step occupancy / KV-pool utilization (observability layer)
            "batch_occupancy": (self.scheduler.num_running
                                / max(1, self.config.scheduler.max_num_seqs)),
            "kv_blocks_total": self.runner.num_blocks,
            "kv_blocks_free": self.scheduler.num_free_blocks,
            "kv_pool_bytes": (self.runner.num_blocks
                              * self.config.cache.block_size
                              * self.config.model.kv_bytes_per_token),
            **(self.window_counters.snapshot(self.scheduler)
               if self.window_counters is not None else {}),
            **({"prefix_lookups_bypassed_total":
                self.scheduler.allocator.lookups_bypassed}
               if self.scheduler.bypass_prefix else {}),
            # unified ragged path: dispatch counts + live tokens over the
            # token budget (engine/metrics.py turns these into
            # vllm:ragged_* series)
            "ragged_dispatches_total": self.ragged_dispatches,
            "ragged_narrow_dispatches_total": self.ragged_narrow_dispatches,
            "ragged_live_tokens_total": self.ragged_live_tokens,
            "ragged_attn_walks_total": self.ragged_attn_walks,
            "ragged_attn_narrow_walks_total": self.ragged_attn_narrow_walks,
            "ragged_attn_windows_total": self.ragged_attn_windows,
            "ragged_attn_interior_windows_total":
                self.ragged_attn_interior_windows,
            "decode_dispatches_total": self.decode_dispatches,
            "decode_attn_calls_total": self.decode_attn_calls,
            "decode_attn_slab_calls_total": self.decode_attn_slab_calls,
            "decode_prepared_launches_total": self.decode_prepared_launches,
            "decode_ahead_launches_total": self.decode_ahead_launches,
            "intake_requests_total": self.intake_requests,
            "arrivals_behind_queued_decode_total":
                self.arrivals_behind_queued_decode,
            "ragged_landing_arrivals_total": self.ragged_landing_arrivals,
            "step_phases": self.clock.snapshot(),
            "slow_step_seconds": {k: dict(v) for k, v
                                  in self.clock.slow_seconds.items()},
            "ragged_stream_utilization": (
                self.ragged_live_tokens
                / max(1, self.ragged_dispatches
                      * self.config.scheduler.max_num_batched_tokens)
            ),
        }
        for name in ("moe", "loop"):
            counters = getattr(self.runner, name, None)
            if counters is not None:
                out.update(counters.snapshot())
        out.update(self.recurrent_stats())
        if self.latent is not None:
            out.update(self.latent.snapshot())
        if self.host_kv is not None:
            out["cpu_cache_usage_perc"] = self.host_kv.usage
            out["cpu_prefix_cache_hits_total"] = self.host_kv.hits
            out["cpu_prefix_cache_queries_total"] = self.host_kv.queries
        if self.host_kv is not None or self.remote_kv is not None:
            out["kv_tier"] = self.tier_stats()
        if self.perf is not None:
            out["perf"] = self.perf.stats_fields()
            out["tenants"] = self.tenant_stats()
        return out

    def recurrent_stats(self) -> dict:
        """What a hybrid stack's recurrent layers ran and hold
        (engine/tracing.py RecurrentCounters); {} for every other model."""
        if self.recurrent is None:
            return {}
        return self.recurrent.snapshot(
            self.scheduler.allocator.lookups_bypassed)

    def tenant_stats(self) -> dict:
        """Per-tenant attribution snapshot (tokens by phase, chip-seconds,
        live KV blocks, request/queue-time sums), top-K folded — feeds
        ``vllm:tenant_*`` series, ``/debug/tenants`` and the fleet view.
        Empty-shaped when perf accounting is off."""
        if self.perf is None:
            return {"enabled": False, "tenants": {}}
        kv: dict[str, int] = {}
        for seq in self.scheduler.seqs.values():
            kv[seq.tenant] = kv.get(seq.tenant, 0) + len(seq.block_ids)
        return self.perf.tenant_fields(kv_blocks=kv)

    def tier_stats(self) -> dict:
        """Tiered-KV snapshot: per-tier hit/miss/demote/promote counters,
        byte-accounted traffic, and the prefetch pipeline's latency state.
        Feeds vllm:kv_tier_hit_ratio{tier} / vllm:kv_tier_bytes_total
        {tier,direction} / vllm:kv_prefetch_seconds, the /debug/perf
        ``kv_tier`` block, and (through /metrics) the router's
        tier-weighted prefix scoring."""
        alloc = self.scheduler.allocator
        tiers: dict = {
            "hbm": {
                "hits": alloc.prefix_hits,
                "queries": alloc.prefix_queries,
                "demotions": self.hbm_demotions,
                "evictions": alloc.evictions,
                "usage": alloc.usage,
            },
        }
        if self.host_kv is not None:
            tiers["host"] = {
                "hits": self.host_kv.hits,
                "queries": self.host_kv.queries,
                "demotions": self.host_kv.demotions,
                "evictions": self.host_kv.evictions,
                "usage": self.host_kv.usage,
                "bytes_used": self.host_kv.used_bytes,
                "bytes_capacity": self.host_kv.capacity_bytes,
            }
        if self.remote_kv is not None:
            tiers["remote"] = {
                "hits": self.remote_kv.hits,
                "queries": self.remote_kv.queries,
            }
        prefetch = None
        if self._prefetcher is not None:
            total = self.prefetch_seconds_sum
            prefetch = {
                "submitted": self._prefetcher.submitted,
                "committed": self._prefetcher.committed,
                "dropped": self._prefetcher.dropped,
                "in_flight": len(self._prefetcher.jobs),
                "blocks": self.prefetch_blocks,
                "count": self.prefetch_count,
                "seconds_sum": total,
                "stall_seconds": self.prefetch_stall_seconds,
                # share of prefetch wall time that overlapped useful engine
                # work (1.0 = the serving loop never waited on a tier)
                "overlap_fraction": (
                    max(0.0, 1.0 - self.prefetch_stall_seconds / total)
                    if total > 0 else 1.0
                ),
                "hist_buckets": list(self._PREFETCH_BUCKETS),
                "hist_counts": list(self.prefetch_hist),
            }
        return {
            "tiers": tiers,
            "bytes": {f"{t}_{d}": v
                      for (t, d), v in sorted(self.tier_bytes.items())},
            "prefetch": prefetch,
        }

    # -- sleep mode (frees HBM; reference semantics: engines release device
    #    memory on /sleep and restore on /wake_up, request.py:1027-1114) ----
    def sleep_mode(self, level: int = 1) -> None:
        """level 1: drop the KV pool (largest HBM allocation), keep weights;
        level 2: drop weights too. Refuses while requests are in flight."""
        if self.has_unfinished():
            raise RuntimeError("cannot sleep with unfinished requests")
        from production_stack_tpu.engine.kv_cache import (
            PrefixCachingBlockAllocator,
        )

        # the recurrent layers' per-slot state goes and comes back with
        # the pool (one pytree): no sequence is alive across a sleep
        self.runner.drop_kv()
        bypassed = self.scheduler.allocator.lookups_bypassed
        self.scheduler.allocator = PrefixCachingBlockAllocator(
            self.runner.num_blocks, self.config.cache.block_size,
            self.config.cache.enable_prefix_caching,
            bypass_prefix=self.scheduler.bypass_prefix,
        )
        self.scheduler.allocator.lookups_bypassed = bypassed
        if self.window:
            self.scheduler.window_allocator = PrefixCachingBlockAllocator(
                self.runner.window_blocks, self.config.cache.block_size,
                bypass_prefix=True)
        self._wire_tier_hooks()  # the rebuilt allocator must keep demoting
        if level >= 2:
            self.runner.drop_params()
        self.sleep_level = level

    def wake_mode(self) -> None:
        self.runner.restore_params()
        self.runner.restore_kv()
        self.sleep_level = 0

    def _dense_len(self, n: int) -> int:
        """The padded length a dense (cache-free) scoring pass over ``n``
        tokens compiles at: powers of two from 128, within the model's
        positions."""
        return min(max(128, 1 << (n - 1).bit_length()),
                   self.config.model.max_model_len)

    def embed(self, prompt_token_ids: list[int]) -> "np.ndarray":
        """Mean-pooled final hidden state — the /v1/embeddings surface (the
        reference proxies this to vLLM embedding models; a causal LM's
        pooled hidden is the standard fallback encoder)."""
        import numpy as np

        S = self._dense_len(len(prompt_token_ids))
        tokens = np.zeros((1, S), np.int32)
        tokens[0, : len(prompt_token_ids)] = prompt_token_ids
        mask = np.zeros((1, S), np.int32)
        mask[0, : len(prompt_token_ids)] = 1
        return self.runner.pooled_embed(tokens, mask)[0]

    def choice_logprobs(self, prompt_token_ids: list[int],
                        choices_ids: list[list[int]]) -> list[float]:
        """log P(choice | prompt) for each choice, teacher-forced in one
        batched dense pass — the guided_choice scoring primitive. Sequence-
        level (not a greedy token walk): the server selects or samples
        among choices from these exact probabilities."""
        import numpy as np

        n = len(choices_ids)
        N = 1 << (n - 1).bit_length() if n else 1  # pow-2 compile classes
        total = len(prompt_token_ids) + max(len(c) for c in choices_ids)
        S = self._dense_len(total)
        if S < total:  # past the model's positions: the pass runs dense,
            # so pad to the next power of two all the same
            S = 1 << (total - 1).bit_length()
        tokens = np.zeros((N, S), np.int32)
        cont = np.zeros((N, S), bool)
        p = len(prompt_token_ids)
        for i, c in enumerate(choices_ids):
            tokens[i, : p + len(c)] = list(prompt_token_ids) + list(c)
            cont[i, p : p + len(c)] = True
        return self.runner.sequence_logprobs(tokens, cont)[:n].tolist()

    def prompt_logprobs(self, prompt_token_ids: list[int]) -> list:
        """Logprob entries for ``prompt_token_ids[1:]`` (teacher-forced;
        token 0 has no prediction) — the completions ``echo`` +
        ``logprobs`` surface. Entries use the same (lp, [(id, lp)..])
        shape generation produces. Pads to a power of two so the dense
        scoring program compiles per size class, like choice_logprobs."""
        n = len(prompt_token_ids)
        if n < 2:
            return []
        S = 1 << (n - 1).bit_length()
        tokens = np.zeros((1, S), np.int32)
        tokens[0, :n] = prompt_token_ids
        tok_lps, ids, lps = self.runner.prompt_logprobs(tokens)
        return [_lp_row((tok_lps, ids, lps), p) for p in range(n - 1)]

    def warmup(self) -> None:
        """Pre-compile every serving shape variant so no live request pays a
        compile: the ragged program at each stream width and the decode
        program, greedy and sampled, and their static-flag variants."""
        # the admission bound is client back-pressure; warmup's internal
        # bursts must not trip it (a small --max-queue-len would otherwise
        # kill the server at startup)
        sched_cfg = self.config.scheduler
        bound, sched_cfg.max_queue_len = sched_cfg.max_queue_len, 0
        try:
            self._warmup_impl()
            if self.perf is not None:
                # every serving variant is compiled now: later compiles are
                # unexpected recompiles (an alertable bug signal)
                self.perf.mark_steady()
        finally:
            sched_cfg.max_queue_len = bound

    def _warmup_impl(self) -> None:
        import numpy as np

        rng = np.random.default_rng(0)
        sched = self.config.scheduler
        vocab = self.config.model.vocab_size
        # forces two decode steps: one launched from the host's tokens,
        # one prepared under it and launched from the device's
        decoding = 2 * max(sched.multi_step, 1) + 1
        longest = max(self.config.model.max_model_len
                      - 2 * sched.multi_step - 2, 1)

        def run(prompts, temperature, max_tokens=decoding, **feature):
            sp = SamplingParams(temperature=temperature,
                                max_tokens=max_tokens, ignore_eos=True,
                                **feature)
            for i, p in enumerate(prompts):
                self.add_request(f"warmup-{time.monotonic_ns()}-{i}",
                                 prompt_token_ids=p, sampling=sp)
            while self.has_unfinished():
                self.step()

        def packed(total, spans=1):
            """Prompts of ``total`` tokens together (as far as slots and
            ``max_model_len`` allow), at least ``spans`` of them: what one
            ragged step packs when they arrive together."""
            k = min(max(spans, -(-total // longest)), sched.max_num_seqs,
                    total)
            return [rng.integers(
                1, vocab, min(total // k + (i < total % k), longest)).tolist()
                for i in range(k)]

        # token totals that land a ragged step in each stream width
        # (SchedulerConfig.ragged_stream_widths), narrowest first: the
        # 8-token prompts of the feature runs below, then one token over
        # each width but the budget
        lands = [8] + [w + 1 for w in sched.ragged_stream_widths[:-1]]
        # the ragged program's signature is shape-independent of the
        # traffic but for the stream's width (slots always max_num_seqs):
        # ONE greedy + ONE sampled run a width. The sampled run is a mixed
        # multi-prompt batch: same signature, but exercises the packed
        # multi-span path once before traffic does. A narrow width's runs
        # end at their first token (the decode program is not theirs). The
        # feature-variant runs below flow through the same step and
        # compile their static-flag variants, at every width: grammar and
        # controls. Logprobs ride every ragged dispatch, a penalty gates
        # on decode rows (a prompt alone runs the plain program) and
        # speculation's verify columns ride every dispatch: those three
        # reach no ragged signature but these.
        for total in lands[:-1]:
            run(packed(total), 0.0, max_tokens=1)
            run(packed(total, spans=4), 0.7, max_tokens=1)
        # the budget's own width: as many prompts as make it up, where
        # the model's positions are fewer than the budget
        run(packed(sched.max_num_batched_tokens), 0.0)
        run(packed(sched.max_num_batched_tokens, spans=4), 0.7)
        # speculative decoding needs no dedicated warmup program: verify is
        # fused into the ragged step and verify_idx rides EVERY dispatch,
        # so the runs above already compiled the verify-bearing signature.
        # Still run one repetitive greedy prompt so a draft-carrying span
        # (propose → pack → verify → accept) executes end-to-end before
        # live traffic does.
        if self._spec is not None:
            motif = rng.integers(1, vocab, 8).tolist()
            sp = SamplingParams(temperature=0.0, max_tokens=8,
                                ignore_eos=True)
            self.add_request(f"warmup-spec-{time.monotonic_ns()}",
                             prompt_token_ids=motif * 4, sampling=sp)
            while self.has_unfinished():
                self.step()
        # logprob decode variants (static want_logprobs flag), greedy and
        # sampled; the ragged program carries logprobs unconditionally.
        # Combinations with penalties/controls compile lazily if ever
        # used (same tradeoff as the penalties x controls cross).
        for temp in (0.0, 0.7):
            run(packed(lands[0]), temp, logprobs=5)
        # guided-decoding variants (static use_grammar flag): the first
        # token's mask + the fused decode FSM advance, greedy and sampled.
        # Also pays the one-time vocab byte-image build here instead of
        # on the first live guided request.
        for temp in (0.0, 0.7):
            run(packed(lands[0]), temp, guided_regex="[ -~]*")
            for total in lands[1:]:  # the mask at the wider streams
                run(packed(total), temp, max_tokens=1,
                    guided_regex="[ -~]*")
        # penalised decode variant (static use_penalties flag)
        run(packed(lands[0]), 0.0, presence_penalty=0.5)
        # token-controls variants (static use_controls flag): the first
        # logit_bias/allowed_token_ids request must not stall on a
        # mid-traffic recompile of the fused decode + ragged graphs
        # guided-choice scorer: one representative (N, S) variant so the
        # first guided request doesn't compile mid-traffic
        self.choice_logprobs([1, 2, 3, 4], [[5], [6, 7]])
        for temp in (0.0, 0.7):  # greedy and sampled control variants
            run(packed(lands[0]), temp, logit_bias={1: 0.0})
            for total in lands[1:]:  # and at the wider streams
                run(packed(total), temp, max_tokens=1, logit_bias={1: 0.0})

    # -- convenience for tests / offline use ---------------------------------
    def generate(
        self,
        prompts: list[str] | list[list[int]],
        sampling: Optional[SamplingParams] = None,
        max_steps: int = 100_000,
    ) -> dict[str, list[int]]:
        seqs = {}
        for i, p in enumerate(prompts):
            rid = f"offline-{i}"
            if isinstance(p, str):
                seqs[rid] = self.add_request(rid, prompt=p, sampling=sampling)
            else:
                seqs[rid] = self.add_request(rid, prompt_token_ids=p, sampling=sampling)
        for _ in range(max_steps):
            if not self.has_unfinished():
                break
            self.step()
        return {rid: s.output_token_ids for rid, s in seqs.items()}
