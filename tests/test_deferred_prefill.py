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


@pytest.mark.parametrize("multi_step", [1, 2])
def test_prepared_decode_token_identical(multi_step):
    """Decode steps launched prepared, from the tokens the step before
    left on the device, must produce the tokens of the same engine run in
    order (its arrival probe held true), including seeded sampling and
    mid-stream membership changes."""
    from production_stack_tpu.engine.config import SchedulerConfig

    def make(in_order):
        cfg = EngineConfig(
            model=ModelConfig.from_pretrained("tiny-llama"),
            cache=CacheConfig(block_size=4, num_blocks=128),
            scheduler=SchedulerConfig(
                max_num_seqs=4, max_num_batched_tokens=64,
                multi_step=multi_step,
            ),
            mesh=MeshConfig(data=1, tensor=1),
        )
        engine = LLMEngine(cfg, mesh=build_mesh(cfg.mesh), num_blocks=128)
        if in_order:
            engine.arrival_probe = lambda: True
        return engine

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
        return toks, engine.decode_prepared_launches

    ref, none = run(make(True))
    got, prepared = run(make(False))
    assert got == ref and none == 0 and prepared >= 2
    for i in range(len(prompts)):
        assert len(ref[f"r{i}"]) == sp.max_tokens - 4 * i


# -- what a step resolves is returned once its own program is launched ---------
# A mixed run: prompts arrive while others decode, one asks for a single
# token, one for log-probabilities with seeded sampling. The tokens of
# every request, with its finish and its log-probabilities flag, are what
# step() returned at the commit before the prepared order (21db38c, its
# PARENT_EVENTS by request): [tokens, has logprobs], and one finish, last.

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
    "one_token_a_step": dict(),
    "two_tokens_a_step": dict(multi_step=2),
}
PARENT_TOKENS = {
    "r0": ([400, 400, 400, 83, 385, 27], False), "r1": ([27], False),
    "r2": ([408, 83, 298, 419], True), "r3": ([233, 415, 464], False),
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
    """Drive step() by hand; [step, request, tokens, finished, has
    logprobs] in the order step() returned them."""
    events = []
    for i in range(64):
        for rid, prompt, sp in ARRIVALS.get(i, ()):
            engine.add_request(rid, prompt_token_ids=prompt, sampling=sp)
        if not engine.has_unfinished() and i > max(ARRIVALS):
            break
        events.extend(
            [i, o.request_id, list(o.new_token_ids), o.finished,
             o.new_logprobs is not None] for o in engine.step())
    assert not engine.has_unfinished()
    return events


@pytest.mark.parametrize("order", ["prepared", "in_order"])
@pytest.mark.parametrize("case", list(SCHEDULES))
def test_step_returns_what_the_parent_generated(case, order):
    """Same tokens, same log-probabilities flag, one finish a request and
    that one last, whichever order the decode steps were launched in."""
    engine = make_scheduled_engine(**SCHEDULES[case])
    if order == "in_order":
        engine.arrival_probe = lambda: True
    events = run_arrivals(engine)
    for rid, (tokens, has_lp) in PARENT_TOKENS.items():
        mine = [e for e in events if e[1] == rid]
        assert [t for e in mine for t in e[2]] == tokens, rid
        assert {e[4] for e in mine} == {has_lp}
        assert [e[3] for e in mine].count(True) == 1 and mine[-1][3]
    assert [e[0] for e in events] == sorted(e[0] for e in events)
    # (two tokens a step: the arrivals leave no two decode steps in a row)
    assert (engine.decode_prepared_launches > 0) == (
        order == "prepared" and case == "one_token_a_step")


def test_a_decode_step_returns_the_first_token_once_it_has_launched():
    """The prompt completes in ragged step N; step N+1 waits that program
    out, launches the first decode program and returns the prompt's first
    token without waiting for it; step N+2 prepares its inputs, waits, and
    launches at the landing before it looks at the landed tokens."""
    engine = make_scheduled_engine()
    log = []
    real_enter = engine.clock.enter

    def enter(phase, **attrs):
        log.append(phase)
        return real_enter(phase, **attrs)

    engine.clock.enter = enter
    engine.add_request("r0", prompt_token_ids=[1, 2, 3, 4, 5],
                       sampling=_sp(6))
    assert engine.step() == [] and engine._pending_ragged is not None
    del log[:]
    returned = engine.step()  # resolves the ragged step, then decodes
    # wait (the ragged step), ..., build, snapshot, commit, launch and out
    assert log.count("wait") == 1 and log.index("wait") < log.index("launch")
    assert log[-2:] == ["launch", "postprocess"]
    assert [(o.request_id, o.new_token_ids) for o in returned] == [
        ("r0", [400])]
    assert engine._pending_decode is not None
    assert (engine.decode_dispatches, engine.decode_prepared_launches) == (
        1, 0)
    del log[:]
    returned = engine.step()
    assert log[log.index("commit"):] == [
        "commit", "wait", "postprocess", "launch", "postprocess"]
    assert [(o.request_id, o.new_token_ids) for o in returned] == [
        ("r0", [400])]
    assert (engine.decode_dispatches, engine.decode_prepared_launches) == (
        2, 1)
