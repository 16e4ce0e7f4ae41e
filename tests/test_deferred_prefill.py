"""Deferred prefill resolution: the cross-step races the dispatch
pipelining introduces (engine/engine.py _pending_prefill). A prefill
dispatch's sampled tokens land one step after scheduler-visible state
advances, so aborts, preemption, and max_tokens=1 finishes can all occur
while the dispatch is in flight."""

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
        scheduler=SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=64,
                                  prefill_buckets=(16, 32)),
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
    engine.step()  # dispatches the prefill; resolution is pending
    assert engine._pending_prefill is not None
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
    outs = engine._resolve_pending_prefill()
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
    outs = engine._resolve_pending_prefill()
    got = [t for o in outs for t in o.new_token_ids]
    got += [t for o in drain(engine) for t in o.new_token_ids]
    assert got == ref


def test_empty_schedule_flushes_pending():
    engine = make_engine()
    sp = SamplingParams(temperature=0.0, max_tokens=1, ignore_eos=True)
    engine.add_request("r0", prompt_token_ids=[1, 2, 3], sampling=sp)
    engine.step()
    assert engine._pending_prefill is not None
    outs = engine.step()  # schedule sees RUNNING seq -> resolves + finishes
    assert engine._pending_prefill is None
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
                prefill_buckets=(16, 32), multi_step=2,
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
