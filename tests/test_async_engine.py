"""AsyncEngine facade: admission atomicity and cancellation hygiene.

The r3 advisor found that a client disconnect during ``admit_batch``
(asyncio.CancelledError while awaiting admission) left the stream queues
registered forever and the admitted requests running with no consumer.
These tests pin the BaseException cleanup path.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import threading

import pytest

from production_stack_tpu.engine.async_engine import AsyncEngine
from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.weights import init_or_load
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh


@pytest.fixture(scope="module")
def setup():
    cfg = EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=128),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_num_batched_tokens=32,
            ),
        mesh=MeshConfig(data=1, tensor=1),
    )
    mesh = build_mesh(cfg.mesh)
    params = init_or_load(cfg.model, mesh, seed=0)
    return cfg, mesh, params


def test_admit_batch_cancelled_mid_admission_cleans_up(setup):
    """Cancel while awaiting admission: streams deregistered, admitted
    requests aborted (the aborts are queued behind the add on the intake
    queue, so ordering is deterministic)."""
    cfg, mesh, params = setup
    eng = LLMEngine(cfg, mesh=mesh, params=params,
                    num_blocks=cfg.cache.num_blocks)
    sp = SamplingParams(temperature=0.0, max_tokens=64, ignore_eos=True)

    async def fn():
        ae = AsyncEngine(eng)
        await ae.start()
        try:
            # wedge the worker thread so the admission call can't complete
            # before we cancel
            release = threading.Event()
            ae.intake.put((
                "call",
                (lambda e: release.wait(10), concurrent.futures.Future()),
            ))
            task = asyncio.ensure_future(ae.admit_batch([
                ("cancelled-1", [1, 2, 3], sp, 0),
                ("cancelled-2", [4, 5], sp, 0),
            ]))
            await asyncio.sleep(0.2)
            assert set(ae.streams) == {"cancelled-1", "cancelled-2"}
            task.cancel()
            with pytest.raises(asyncio.CancelledError):
                await task
            # streams deregistered synchronously on the cancel path
            assert ae.streams == {}
            release.set()
            # the worker processes add_all, then the queued aborts: the
            # engine must end up empty without anyone consuming outputs
            for _ in range(100):
                busy = await ae.run_on_engine(
                    lambda e: e.has_unfinished()
                )
                if not busy:
                    break
                await asyncio.sleep(0.05)
            assert not busy
        finally:
            ae.stop()
        return True

    assert asyncio.run(fn())


def test_admit_batch_failure_aborts_siblings(setup):
    """All-or-nothing: a failing request aborts the already-added ones and
    deregisters every stream."""
    cfg, mesh, params = setup
    eng = LLMEngine(cfg, mesh=mesh, params=params,
                    num_blocks=cfg.cache.num_blocks)
    good = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)

    async def fn():
        ae = AsyncEngine(eng)
        await ae.start()
        try:
            with pytest.raises(Exception):
                await ae.admit_batch([
                    ("sib-1", [1, 2], good, 0),
                    # over-long prompt: add_request rejects it
                    ("sib-2", list(range(10_000)), good, 0),
                ])
            assert ae.streams == {}
            assert not await ae.run_on_engine(lambda e: e.has_unfinished())
        finally:
            ae.stop()
        return True

    assert asyncio.run(fn())


# -- a decode step returns once its program is launched: what the landing
# before it resolved reaches the streams while the device runs, once only ------

def _collect(ae, prompt, sp, rid):
    async def go():
        items = []
        try:
            async for out in ae.generate(prompt, sp, request_id=rid):
                items.append(out)
        except Exception as e:  # what the stream raised, in its place
            items.append(e)
        return items
    return go()


@pytest.mark.parametrize("order", ["prepared", "in_order"])
def test_streams_get_what_the_engine_produced_once_and_in_order(setup, order):
    """Prompts arrive while others decode (one wants a single token, one
    log-probabilities): every stream receives exactly the outputs the
    engine thread produced for it, in that order, whether the decode
    steps were launched prepared or, the probe held true, in order."""
    cfg, mesh, params = setup
    eng = LLMEngine(cfg, mesh=mesh, params=params,
                    num_blocks=cfg.cache.num_blocks)
    produced = {}
    real = eng._postprocess

    def postprocess(*a, **kw):
        outs = real(*a, **kw)
        for o in outs:
            produced.setdefault(o.request_id, []).append(o)
        return outs

    eng._postprocess = postprocess
    greedy = dict(temperature=0.0, ignore_eos=True)
    requests = [
        ("s0", [1, 2, 3, 4, 5], SamplingParams(max_tokens=12, **greedy)),
        ("s1", [9, 8, 7], SamplingParams(max_tokens=1, **greedy)),
        ("s2", [3, 1, 4, 1, 5, 9, 2], SamplingParams(
            max_tokens=8, temperature=0.8, top_k=30, seed=7, logprobs=2,
            ignore_eos=True)),
        ("s3", [6, 6, 6, 6], SamplingParams(max_tokens=6, **greedy)),
    ]

    async def fn():
        ae = AsyncEngine(eng)
        await ae.start()
        assert eng.arrival_probe() is False  # the worker's: nothing queued
        if order == "in_order":
            eng.arrival_probe = lambda: True
        try:
            tasks = []
            for rid, prompt, sp in requests:
                tasks.append(asyncio.ensure_future(
                    _collect(ae, prompt, sp, rid)))
                await asyncio.sleep(0.05)  # the others are decoding by now
            return await asyncio.wait_for(asyncio.gather(*tasks), 120)
        finally:
            ae.stop()

    got = asyncio.run(fn())
    assert eng.arrival_probe is None  # stop() gives step() back to its caller
    assert eng.decode_dispatches >= 8
    assert (eng.decode_prepared_launches > 0) == (order == "prepared")
    for (rid, _, sp), items in zip(requests, got):
        assert items == produced[rid]  # the same objects, each once
        assert sum(len(o.new_token_ids) for o in items) == sp.max_tokens
        assert [o.finished for o in items].count(True) == 1
        assert items[-1].finished
        if sp.logprobs is not None:
            assert all(o.new_logprobs for o in items)


def test_a_step_that_raises_at_a_landing_delivers_nothing_twice(setup):
    """The first decode step is launched and returns the prompt's first
    token; the step after it fails waiting for that program: the stream
    has that token once, then the error, and nothing after it."""
    cfg, mesh, params = setup
    eng = LLMEngine(cfg, mesh=mesh, params=params,
                    num_blocks=cfg.cache.num_blocks)
    handed = []

    def fetch_decode(pending):
        handed.append(eng.decode_dispatches)
        raise RuntimeError("device lost")

    eng._fetch_decode = fetch_decode
    sp = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)

    async def fn():
        ae = AsyncEngine(eng)
        await ae.start()
        try:
            items = await asyncio.wait_for(
                _collect(ae, [1, 2, 3, 4, 5], sp, "boom"), 60)
            await asyncio.sleep(0.2)  # anything late would land now
            busy = await ae.run_on_engine(lambda e: e.has_unfinished())
            return items, busy, dict(ae.streams)
        finally:
            ae.stop()

    items, busy, streams = asyncio.run(fn())
    assert handed == [1]  # the one program launched before the failing wait
    assert len(items) == 2
    first, err = items
    assert len(first.new_token_ids) == 1 and not first.finished
    assert isinstance(err, ValueError) and "device lost" in str(err)
    assert not busy and streams == {}
