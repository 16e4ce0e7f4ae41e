"""Deferred resolution of a prompt's step: the cross-step races the
dispatch pipelining introduces (engine/engine.py _pending_ragged). A
ragged dispatch's sampled tokens land one step after scheduler-visible
state advances, so aborts, preemption, and max_tokens=1 finishes can all
occur while the dispatch is in flight."""

import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.sequence import SequenceStatus
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh


def make_engine(num_blocks=64):
    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=num_blocks),
        scheduler=SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=64),
        mesh=MeshConfig(data=1, tensor=1),
    )
    return LLMEngine(cfg, mesh=build_mesh(cfg.mesh), num_blocks=num_blocks)


def drain(engine, limit=64):
    outs = []
    steps = 0
    while engine.has_unfinished() and steps < limit:
        outs.extend(engine.step())
        steps += 1
    assert not engine.has_unfinished()
    return outs


def test_max_tokens_1_resolves_without_decode():
    """The deferred first token IS the whole completion; the seq lands in
    the decode batch the same step it resolves-finished (RUNNING filter)."""
    engine = make_engine()
    sp = SamplingParams(temperature=0.0, max_tokens=1, ignore_eos=True)
    engine.add_request("r0", prompt_token_ids=[1, 2, 3, 4, 5], sampling=sp)
    outs = drain(engine)
    mine = [o for o in outs if o.request_id == "r0"]
    assert sum(len(o.new_token_ids) for o in mine) == 1
    assert sum(o.finished for o in mine) == 1


def test_abort_while_prefill_in_flight():
    engine = make_engine()
    sp = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)
    engine.add_request("r0", prompt_token_ids=[1, 2, 3], sampling=sp)
    engine.step()  # dispatches the prompt's step; resolution is pending
    assert engine._pending_ragged is not None
    engine.abort_request("r0")
    outs = engine.step()  # resolve must skip the aborted seq
    assert not any(o.request_id == "r0" and o.new_token_ids for o in outs)
    assert not engine.has_unfinished()


def test_finish_while_preempted_is_not_resurrected():
    """A seq preempted while its final prefill dispatch is in flight, whose
    deferred token then triggers a stop, must finish exactly once — not be
    re-admitted from the waiting deque and generated again."""
    engine = make_engine()
    sp = SamplingParams(temperature=0.0, max_tokens=1, ignore_eos=True)
    seq = engine.add_request("r0", prompt_token_ids=[1, 2, 3, 4, 5],
                             sampling=sp)
    engine.step()  # prefill dispatched, pending; seq is RUNNING
    assert seq.status is SequenceStatus.RUNNING
    # simulate pool pressure preempting it before resolution
    engine.scheduler._preempt(seq)
    assert seq in engine.scheduler.waiting
    outs = engine._resolve_pending_ragged()
    mine = [o for o in outs if o.request_id == "r0"]
    assert sum(o.finished for o in mine) == 1
    assert seq.status.is_finished
    assert seq not in engine.scheduler.waiting  # no resurrection
    # draining produces NOTHING further for r0
    more = drain(engine)
    assert not any(o.request_id == "r0" for o in more)


def test_preempted_unfinished_keeps_deferred_token():
    """Preempted mid-flight WITHOUT a stop: the deferred token is appended
    (it becomes the recompute path's pending decode input) and the final
    output is identical to an undisturbed run."""
    engine = make_engine()
    sp = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)
    ref_engine = make_engine()
    ref_engine.add_request("ref", prompt_token_ids=[1, 2, 3, 4, 5],
                           sampling=sp)
    ref = [t for o in drain(ref_engine) for t in o.new_token_ids]

    seq = engine.add_request("r0", prompt_token_ids=[1, 2, 3, 4, 5],
                             sampling=sp)
    engine.step()
    engine.scheduler._preempt(seq)
    outs = engine._resolve_pending_ragged()
    got = [t for o in outs for t in o.new_token_ids]
    got += [t for o in drain(engine) for t in o.new_token_ids]
    assert got == ref


def test_empty_schedule_flushes_pending():
    engine = make_engine()
    sp = SamplingParams(temperature=0.0, max_tokens=1, ignore_eos=True)
    engine.add_request("r0", prompt_token_ids=[1, 2, 3], sampling=sp)
    engine.step()
    assert engine._pending_ragged is not None
    outs = engine.step()  # schedule sees RUNNING seq -> resolves + finishes
    assert engine._pending_ragged is None
    assert any(o.finished for o in outs)


def test_chained_decode_token_identical():
    """chain_decode=true (off by default, unmeasured on the chip) must
    produce identical tokens, including seeded sampling and mid-stream
    membership changes."""
    from production_stack_tpu.engine.config import SchedulerConfig

    def make(chain):
        cfg = EngineConfig(
            model=ModelConfig.from_pretrained("tiny-llama"),
            cache=CacheConfig(block_size=4, num_blocks=128),
            scheduler=SchedulerConfig(
                max_num_seqs=4, max_num_batched_tokens=64,
                multi_step=2,
                chain_decode=chain,
            ),
            mesh=MeshConfig(data=1, tensor=1),
        )
        return LLMEngine(cfg, mesh=build_mesh(cfg.mesh), num_blocks=128)

    sp = SamplingParams(temperature=0.8, top_k=30, seed=7, max_tokens=9,
                       ignore_eos=True)
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7]]

    def run(engine):
        for i, p in enumerate(prompts):
            # staggered max_tokens force a mid-stream membership change
            spi = SamplingParams(**{**sp.__dict__,
                                    "max_tokens": sp.max_tokens - 4 * i})
            engine.add_request(f"r{i}", prompt_token_ids=p, sampling=spi)
        toks = {f"r{i}": [] for i in range(len(prompts))}
        steps = 0
        while engine.has_unfinished() and steps < 64:
            for o in engine.step():
                if o.request_id in toks:
                    toks[o.request_id].extend(o.new_token_ids)
            steps += 1
        return toks

    ref = run(make(False))
    got = run(make(True))
    assert got == ref
    for i in range(len(prompts)):
        assert len(ref[f"r{i}"]) == sp.max_tokens - 4 * i


# -- a step's resolved tokens leave before the wait for the decode program ----
# (LLMEngine.output_sink / _hand_over). A mixed run: prompts arrive while
# others decode, one asks for a single token, one for log-probabilities
# with seeded sampling. What step() returned for it at the parent commit
# (e7d0282, computed from an unpacked `git archive` of it, on the CPU) is
# pinned: [step, request, tokens, finished, has logprobs], in order.

def _sp(max_tokens, **kw):
    kw.setdefault("temperature", 0.0)
    return SamplingParams(max_tokens=max_tokens, ignore_eos=True, **kw)


ARRIVALS = {  # before step n
    0: [("r0", [1, 2, 3, 4, 5], _sp(6)), ("r1", [9, 8, 7], _sp(1))],
    2: [("r2", [3, 1, 4, 1, 5, 9, 2],
         _sp(4, temperature=0.8, top_k=30, seed=7, logprobs=2))],
    4: [("r3", [6, 6, 6, 6], _sp(3))],
}
SCHEDULES = {
    "ragged": dict(),
    "chained": dict(multi_step=2, chain_decode=True),
}
PARENT_EVENTS = {
    "ragged": [
        [1, "r0", [400], False, False], [1, "r1", [27], True, False],
        [1, "r0", [400], False, False], [3, "r2", [408], False, True],
        [3, "r0", [400], False, False], [3, "r0", [83], False, False],
        [3, "r2", [83], False, True], [5, "r3", [233], False, False],
        [5, "r0", [385], False, False], [5, "r2", [298], False, True],
        [5, "r0", [27], True, False], [5, "r2", [419], True, True],
        [5, "r3", [415], False, False], [6, "r3", [464], True, False]],
    "chained": [
        [1, "r0", [400], False, False], [1, "r1", [27], True, False],
        [2, "r0", [400, 400], False, False], [3, "r2", [408], False, True],
        [3, "r0", [83], False, False], [3, "r0", [385, 27], True, False],
        [3, "r2", [83, 298], False, True], [5, "r3", [233], False, False],
        [5, "r2", [419], True, True], [6, "r3", [415, 464], True, False]],
}
# the events that are resolved before a decode program the thread then
# waits for: with a sink they take that way, the others are returned
HANDED_OVER = {
    "ragged": {1: 2, 3: 2, 5: 3},     # step -> leading events of that step
    # chained: step 1 launches and does not wait; step 3's decode program
    # carries logprobs (not chainable), so the thread waits for it
    "chained": {3: 2},
}


def make_scheduled_engine(**sched):
    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=128),
        scheduler=SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=64,
                                  **sched),
        mesh=MeshConfig(data=1, tensor=1))
    return LLMEngine(cfg, mesh=build_mesh(cfg.mesh), num_blocks=128)


def run_arrivals(engine):
    """Drive step() by hand; events in the order they left the engine,
    each with the way it took."""
    events, step = [], [0]

    def log(outs, way):
        events.extend(
            ([step[0], o.request_id, list(o.new_token_ids), o.finished,
              o.new_logprobs is not None], way) for o in outs)

    if engine.output_sink is not None:  # the caller asked for one
        engine.output_sink = lambda outs: log(outs, "sink")
    for i in range(64):
        for rid, prompt, sp in ARRIVALS.get(i, ()):
            engine.add_request(rid, prompt_token_ids=prompt, sampling=sp)
        if not engine.has_unfinished() and i > max(ARRIVALS):
            break
        step[0] = i
        log(engine.step(), "returned")
    assert not engine.has_unfinished()
    return events


@pytest.mark.parametrize("case", list(SCHEDULES))
def test_step_without_a_sink_returns_what_the_parent_returned(case):
    engine = make_scheduled_engine(**SCHEDULES[case])
    assert engine.output_sink is None
    events = run_arrivals(engine)
    assert [e for e, _ in events] == PARENT_EVENTS[case]
    assert {way for _, way in events} == {"returned"}
    assert engine.early_handovers == 0
    assert engine.stats()["early_handovers_total"] == 0


@pytest.mark.parametrize("case", list(SCHEDULES))
def test_a_sink_changes_no_token_and_no_order(case):
    """Same tokens, same log-probabilities flag, same finishes, in the
    same order for every request and over all of them; only the way
    differs, and nothing takes both."""
    engine = make_scheduled_engine(**SCHEDULES[case])
    engine.output_sink = print  # run_arrivals puts its own in its place
    events = run_arrivals(engine)
    assert [e for e, _ in events] == PARENT_EVENTS[case]
    want_ways = []
    for step in sorted({e[0] for e in PARENT_EVENTS[case]}):
        n = sum(e[0] == step for e in PARENT_EVENTS[case])
        early = HANDED_OVER[case].get(step, 0)
        want_ways += ["sink"] * early + ["returned"] * (n - early)
    assert [way for _, way in events] == want_ways
    assert engine.early_handovers == len(HANDED_OVER[case])
    # every request got exactly max_tokens tokens and one finish
    for rid, _, sp in (a for batch in ARRIVALS.values() for a in batch):
        mine = [e for e, _ in events if e[1] == rid]
        assert sum(len(e[2]) for e in mine) == sp.max_tokens
        assert [e[3] for e in mine].count(True) == 1 and mine[-1][3]


def test_first_token_reaches_the_sink_before_the_decode_wait():
    """The prompt completes in ragged step N; in step N+1 its first token
    is handed over under the clock's `deliver` phase after the decode
    program is launched and before the thread waits for it."""
    engine = make_scheduled_engine()
    log = []
    real_enter = engine.clock.enter

    def enter(phase, **attrs):
        log.append(phase)
        return real_enter(phase, **attrs)

    engine.clock.enter = enter
    engine.output_sink = lambda outs: log.append(
        ("sink", [(o.request_id, list(o.new_token_ids)) for o in outs]))
    engine.add_request("r0", prompt_token_ids=[1, 2, 3, 4, 5],
                       sampling=_sp(6))
    assert engine.step() == [] and engine._pending_ragged is not None
    del log[:]
    returned = engine.step()  # resolves the ragged step, then decodes
    sink_at = [i for i, x in enumerate(log) if isinstance(x, tuple)]
    assert len(sink_at) == 1 and log[sink_at[0]] == ("sink", [("r0", [400])])
    assert log[sink_at[0] - 1] == "deliver"
    before, after = log[:sink_at[0]], log[sink_at[0] + 1:]
    # ... wait (the ragged step), build, snapshot, commit, launch,
    # deliver, SINK, wait (the decode step), postprocess
    assert "launch" in before and before.index("wait") < before.index(
        "launch")
    assert after[0] == "wait" and "launch" not in after
    # the decode step's own token is returned, and only that
    assert [(o.request_id, o.new_token_ids) for o in returned] == [
        ("r0", [400])]
    assert engine.early_handovers == 1
