"""Per-request sequence state (host side, control plane)."""

from __future__ import annotations

import dataclasses
import enum
import time
from typing import Optional

from production_stack_tpu.engine.sampling import SamplingParams


class SequenceStatus(enum.Enum):
    WAITING = "waiting"
    PREFILLING = "prefilling"
    # admitted with blocks allocated, but a warm-tier (host/remote) prefix
    # fetch is still in flight on the prefetch executor — the scheduler
    # parks the sequence (neither prefill nor decode touches it) until the
    # engine commits or drops the staged blocks and flips it to PREFILLING
    PREFETCHING = "prefetching"
    RUNNING = "running"  # decoding
    PREEMPTED = "preempted"
    FINISHED_STOPPED = "stop"
    FINISHED_LENGTH = "length"
    FINISHED_ABORTED = "abort"

    @property
    def is_finished(self) -> bool:
        return self in (
            SequenceStatus.FINISHED_STOPPED,
            SequenceStatus.FINISHED_LENGTH,
            SequenceStatus.FINISHED_ABORTED,
        )


@dataclasses.dataclass
class Sequence:
    request_id: str
    prompt_token_ids: list[int]
    sampling: SamplingParams
    arrival_time: float = dataclasses.field(default_factory=time.monotonic)

    adapter_slot: int = 0  # multi-LoRA bank slot; 0 = base model
    # tenant identity resolved at admission (tenancy.resolve_tenant):
    # host-side metadata only — never enters a jitted program's inputs,
    # never read by scheduling. Attribution is observe-only.
    tenant: str = "anonymous"
    # chip-seconds attributed to this sequence so far: its live-token
    # share of every dispatch's wall time (tenancy.split_shares, exact
    # conservation at the tenant level) — feeds the usage ledger
    chip_seconds: float = 0.0
    # compacted token controls (sampling.make_token_controls): or None
    token_ctrl: Optional[tuple] = None
    # constrained decoding: device grammar-bank slot (-1 = unconstrained),
    # current FSM state (generation starts at 0; host mirror of the
    # device-side advance), and the host TokenFsm (prefill-token advance,
    # slot release key)
    grammar_slot: int = -1
    fsm_state: int = 0
    fsm: Optional[object] = None

    output_token_ids: list[int] = dataclasses.field(default_factory=list)
    status: SequenceStatus = SequenceStatus.WAITING
    block_ids: list[int] = dataclasses.field(default_factory=list)
    # where the model's window binds (ModelConfig.window_binds): the window
    # layers' blocks, of a pool of their own, by logical index like
    # block_ids; the first window_released of them were given back (no row
    # to come can see them) and their entries are stale
    window_block_ids: list[int] = dataclasses.field(default_factory=list)
    window_released: int = 0
    num_computed_tokens: int = 0  # tokens whose KV sits in the cache
    num_cached_tokens: int = 0  # prefix-cache hits at admission (for metrics)
    slot: int = -1  # decode slot index, -1 = none
    admit_time: Optional[float] = None  # waiting → scheduled (queue exit)
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    # the engine step (StepClock.step_num, the `engine_step` annotation of
    # a profiler trace) in which each of the three stamps above was taken;
    # a first token is stamped by the step that fetched it, one after the
    # step that dispatched it when the fetch is deferred
    admit_step: Optional[int] = None
    first_token_step: Optional[int] = None
    finish_step: Optional[int] = None
    # the way to the first token, on the same clock and with the engine
    # step beside each stamp: when the event loop put the add on the
    # intake queue; the step that had begun last when the engine thread
    # took it off (arrival_time) and what the thread had done just before
    # (StepClock.last_wait: the kind of dispatch the step it had ended
    # waited for, "none" if that step only launched, "idle" if the thread
    # had waited for work); the launch of the first dispatch that carried
    # a row of the prompt, and the dispatches that carried one until the
    # first token
    enqueue_time: Optional[float] = None
    enqueue_step: Optional[int] = None
    arrival_step: Optional[int] = None
    arrival_after: Optional[str] = None
    first_launch_time: Optional[float] = None
    first_launch_step: Optional[int] = None
    prefill_dispatches: int = 0
    # block ids held at release time (they stay content-addressed in the
    # allocator until evicted — the handle for P→D KV export)
    released_block_ids: list[int] = dataclasses.field(default_factory=list)

    # speculative decoding (engine/spec.py): acceptance EWMA + cold-probe
    # counter driving the adaptive draft width, the stream-token grant the
    # scheduler charged this step, and the drafts actually proposed at
    # pack time (consumed by the next ragged dispatch)
    spec_ewma: float = 1.0
    spec_cold_steps: int = 0
    spec_grant: int = 0
    spec_drafts: list[int] = dataclasses.field(default_factory=list)

    @property
    def token_ids(self) -> list[int]:
        return self.prompt_token_ids + self.output_token_ids

    @property
    def num_tokens(self) -> int:
        return len(self.prompt_token_ids) + len(self.output_token_ids)

    @property
    def num_prompt_tokens(self) -> int:
        return len(self.prompt_token_ids)

    @property
    def prefill_target(self) -> int:
        """Tokens that must be in-cache before decoding can resume.

        Fresh request: the whole prompt (the first output token is sampled
        from the prefill's last logit). Preemption-recompute: everything but
        the newest output token, which becomes the pending decode input."""
        if self.output_token_ids:
            return self.num_tokens - 1
        return self.num_prompt_tokens

    @property
    def prefill_done(self) -> bool:
        return self.num_computed_tokens >= self.prefill_target

    def finish_reason(self) -> Optional[str]:
        if not self.status.is_finished:
            return None
        return self.status.value


@dataclasses.dataclass
class RequestOutput:
    """One step's increment for a request (engine → server layer)."""

    request_id: str
    new_token_ids: list[int]
    finished: bool
    finish_reason: Optional[str]
    num_prompt_tokens: int
    num_output_tokens: int
    num_cached_tokens: int = 0
    tenant: str = "anonymous"  # attribution identity (set on finish)
    chip_seconds: float = 0.0  # attributed dispatch wall time (on finish)
    block_ids: Optional[list[int]] = None  # set on finish (KV export handle)
    # lifecycle stamps (monotonic clock), set on finish like block_ids —
    # the server derives queue/prefill/decode stage histograms from them
    arrival_time: Optional[float] = None
    admit_time: Optional[float] = None
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    enqueue_time: Optional[float] = None
    first_launch_time: Optional[float] = None
    # the engine steps of the stamps above (Sequence.*_step), under the
    # flight record's names: "enqueued", "arrival", "admitted",
    # "first_launch", "first_token", "last_token"; set on finish
    steps: Optional[dict] = None
    # Sequence.arrival_after and .prefill_dispatches, set on finish
    arrival_after: Optional[str] = None
    prefill_dispatches: int = 0
    # aligned with new_token_ids when the request asked for logprobs: each
    # entry is (token_logprob, [(token_id, logprob), ...] top-N) — the
    # server slices top-N down to the request's asked-for count
    new_logprobs: Optional[list] = None
