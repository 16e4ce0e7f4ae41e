"""The engine thread's step clock (engine/tracing.py) over a real tiny
engine on the CPU: phases cover the worker loop, every step has one kind,
the counters reach /metrics and /debug/perf, the timers it replaced still
feed PerfAccountant and the step histogram, programs carry names, request
records name their steps, and none of it changes what is generated."""

import asyncio
import dataclasses
import functools
import time
import types
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from production_stack_tpu.engine import model_runner, tracing
from production_stack_tpu.engine.async_engine import AsyncEngine
from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.server import EngineServer
from production_stack_tpu.engine.tracing import (
    HOST_PHASES,
    STEP_KINDS,
    StepClock,
)
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

PROMPTS = ["hello world", "the quick brown fox jumps over the lazy dog"]
# greedy tokens of the parent commit (3c635ce) for PROMPTS on tiny-llama,
# seed 0, 12 tokens, ignore_eos: the same under the ragged and the
# bucketed attention path (computed from an unpacked `git archive` of the
# parent, on the CPU)
PARENT_TOKENS = [
    [263, 351, 351, 351, 358, 351, 351, 351, 351, 351, 263, 331],
    [218, 400, 218, 400, 218, 400, 430, 36, 319, 218, 400, 218],
]
GREEDY = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
FAMILIES = ("vllm:engine_host_seconds_total",
            "vllm:engine_host_cpu_seconds_total",
            "vllm:engine_device_wait_seconds_total",
            "vllm:engine_idle_seconds_total",
            "vllm:decode_dispatches_total")


def make_config(**kw) -> EngineConfig:
    return EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=512),
        scheduler=SchedulerConfig(
            max_num_seqs=4, max_num_batched_tokens=64,
            prefill_buckets=(32, 64),
        ),
        mesh=MeshConfig(data=1, tensor=1), **kw)


@pytest.fixture(scope="module")
def server():
    return EngineServer(make_config())


async def _with_client(server, fn):
    from aiohttp.test_utils import TestClient, TestServer

    async with TestClient(TestServer(server.build_app())) as client:
        return await fn(client)


def _clock_total(clock: StepClock) -> float:
    return clock.idle_seconds + sum(
        wall for by_phase in clock.seconds.values()
        for wall, _ in by_phase.values())


def _samples(text: str, name: str) -> dict:
    """{label text: value} of one sample name in a /metrics exposition."""
    out = {}
    for line in text.splitlines():
        head, _, value = line.rpartition(" ")
        if head.split("{", 1)[0] == name:
            out[head] = float(value)
    return out


# -- the clock over a worker loop ---------------------------------------------

@pytest.mark.parametrize("chain", [False, True], ids=["unchained", "chained"])
def test_phases_sum_to_the_worker_wall_time_and_each_step_has_one_kind(chain):
    cfg = make_config()
    cfg = dataclasses.replace(cfg, scheduler=dataclasses.replace(
        cfg.scheduler, chain_decode=chain))
    eng = LLMEngine(cfg)
    clock = eng.clock
    seen = []
    # `deliver` is entered mid-step too, when the engine hands what it has
    # resolved to the worker's sink before it waits for a decode program
    order = []
    real_enter = clock.enter
    clock.enter = lambda phase, **kw: (order.append(phase),
                                       real_enter(phase, **kw))[1]

    async def fn():
        ae = AsyncEngine(eng)
        ae.step_observer = seen.append
        t0 = time.monotonic()
        await ae.start()
        outs = []
        for p in PROMPTS:  # one after the other: idle gaps in between
            toks = []
            async for out in ae.generate(eng.tokenizer.encode(p), GREEDY):
                toks.extend(out.new_token_ids)
            outs.append(toks)
            await asyncio.sleep(0.12)
        ae.stop()  # joins the worker: its last iteration has flushed
        return outs, time.monotonic() - t0, ae.step_count

    outs, wall, step_count = asyncio.run(fn())
    assert outs == PARENT_TOKENS
    # one hand-over a prompt: its ragged step is followed by a decode step
    # the thread waits in (chained: the first of a run only launches, so
    # the first token is returned at once and no hand-over is made)
    assert eng.early_handovers == (0 if chain else len(PROMPTS))
    mid = [i for i, p in enumerate(order[:-1])
           if p == "deliver" and order[i + 1] == "wait"]
    assert len(mid) == eng.early_handovers
    assert all(order[i - 1] == "postprocess" and "launch" in order[i - 3:i]
               for i in mid)
    # the worker's whole life is in some phase: host + wait + idle = wall
    assert _clock_total(clock) == pytest.approx(wall, rel=0.02)
    assert clock.idle_seconds > 0.2
    # every step was charged to exactly one kind, and only steps were
    assert sum(clock.steps.values()) == clock.step_num == step_count
    assert clock.steps["decode"] > 0
    assert clock.steps["ragged"] + clock.steps["prefill"] > 0
    assert not clock.in_step
    for kind in STEP_KINDS:
        if not clock.steps[kind]:
            # a kind that never ran holds no step phase (idle iterations
            # flush their intake under "other")
            assert all(w == 0.0 for p, (w, _) in clock.seconds[kind].items()
                       if p != "intake"), kind
    decode = clock.seconds["decode"]
    for phase in ("schedule", "build", "snapshot", "commit", "launch",
                  "postprocess", "deliver", "wait"):
        assert decode[phase][0] > 0.0, phase
    # on-CPU time is part of wall time (summed: a coarse thread clock may
    # charge a tick to a phase shorter than the tick)
    pairs = [v for by_phase in clock.seconds.values()
             for v in by_phase.values()]
    assert 0 < sum(c for _, c in pairs) <= sum(w for w, _ in pairs) + 0.05
    # the step histogram's observer got one positive duration per step,
    # and together they are the steps' phases (idle and intake excluded)
    assert len(seen) == step_count and min(seen) > 0
    in_steps = sum(w for by_phase in clock.seconds.values()
                   for p, (w, _) in by_phase.items() if p != "intake")
    assert sum(seen) == pytest.approx(in_steps, rel=0.02)


def test_a_step_driven_directly_opens_and_closes_its_own_step():
    eng = LLMEngine(make_config(attention_impl="bucketed"))
    outs = eng.generate(PROMPTS, GREEDY)
    assert list(outs.values()) == PARENT_TOKENS
    clock = eng.clock
    assert not clock.in_step and clock.step_num == sum(clock.steps.values())
    assert clock.steps["prefill"] > 0 and clock.steps["decode"] > 0
    assert clock.steps["ragged"] == 0
    assert eng.decode_dispatches == clock.steps["decode"]
    snap = clock.snapshot()
    assert set(snap["seconds"]) == set(STEP_KINDS)
    assert set(snap["seconds"]["decode"]) == {*HOST_PHASES, "wait"}


@pytest.mark.parametrize("impl,record,kind", [
    ("ragged", "record_ragged", "ragged"),
    ("bucketed", "record_prefill", "prefill")])
def test_perf_accountant_receives_the_clocks_seconds(monkeypatch, impl,
                                                     record, kind):
    eng = LLMEngine(make_config(attention_impl=impl))
    calls = {"record_decode": [], "record_ragged": [], "record_prefill": []}
    for name, got in calls.items():
        real = getattr(eng.perf, name)

        def spy(*a, _real=real, _got=got, **kw):
            _got.append(kw["seconds"])
            return _real(*a, **kw)
        monkeypatch.setattr(eng.perf, name, spy)
    assert list(eng.generate(PROMPTS, GREEDY).values()) == PARENT_TOKENS
    # one record per dispatch, as before the clock: same call counts
    steps = eng.clock.steps
    assert len(calls["record_decode"]) == eng.decode_dispatches \
        == steps["decode"] > 0
    assert len(calls[record]) == steps[kind] > 0
    assert len(calls["record_ragged"]) == eng.ragged_dispatches
    assert sum(map(len, calls.values())) == steps["decode"] + steps[kind]
    assert min(calls["record_decode"] + calls[record]) > 0
    # what they received is the clock's snapshot + commit + launch (+ wait
    # where the call fetched): never more than the clock saw
    by = eng.clock.seconds
    for k, got in (("decode", calls["record_decode"]), (kind, calls[record])):
        seen = sum(by[k][p][0]
                   for p in ("snapshot", "commit", "launch", "wait"))
        assert 0 < sum(got) <= seen + 1e-6


# -- what reaches the server's surfaces ---------------------------------------

def test_families_in_metrics_and_debug_perf_only_grow(server):
    async def fn(client):
        first = await (await client.get("/metrics")).text()
        for fam in FAMILIES[:3]:
            kinds = {k for k in STEP_KINDS
                     if any(f'kind="{k}"' in s for s in _samples(first, fam))}
            assert kinds == set(STEP_KINDS), (fam, kinds)
        phases = {p for p in HOST_PHASES if any(
            f'phase="{p}"' in s for s in _samples(first, FAMILIES[0]))}
        assert phases == set(HOST_PHASES)
        for fam in FAMILIES[3:]:
            assert len(_samples(first, fam)) == 1, fam
        perf0 = await (await client.get("/debug/perf")).json()
        r = await client.post("/v1/completions", json={
            "model": "tiny-llama", "prompt": PROMPTS[1], "max_tokens": 12,
            "temperature": 0, "ignore_eos": True})
        assert r.status == 200
        second = await (await client.get("/metrics")).text()
        perf1 = await (await client.get("/debug/perf")).json()
        for fam in FAMILIES:
            a, b = _samples(first, fam), _samples(second, fam)
            assert set(a) == set(b)
            assert all(b[k] >= a[k] for k in a), fam
            assert sum(b.values()) > sum(a.values()), fam
        assert _samples(second, "vllm:unexpected_recompiles_total") == {
            'vllm:unexpected_recompiles_total{model_name="tiny-llama"}': 0.0}
        sp0, sp1 = perf0["step_phases"], perf1["step_phases"]
        assert sp1["idle_seconds"] >= sp0["idle_seconds"]
        assert sum(sp1["steps"].values()) > sum(sp0["steps"].values())
        for kind in STEP_KINDS:
            for phase in (*HOST_PHASES, "wait"):
                s0 = sp0["seconds"][kind][phase]
                s1 = sp1["seconds"][kind][phase]
                assert s1["wall"] >= s0["wall"] and s1["cpu"] >= s0["cpu"]
        # /metrics and /debug/perf say the same thing
        host = sum(s["wall"] for by in sp1["seconds"].values()
                   for p, s in by.items() if p != "wait")
        assert host >= sum(_samples(second, FAMILIES[0]).values()) > 0

    asyncio.run(_with_client(server, fn))


def test_early_handovers_counter_counts_each_hand_over(server):
    """vllm:engine_early_handovers_total on /metrics and `early_handovers`
    on /debug/perf are one plain count: the calls the engine made to its
    output sink, each before a wait for a decode program."""
    name = "vllm:engine_early_handovers_total"

    async def read(client):
        text = await (await client.get("/metrics")).text()
        (value,) = _samples(text, name).values()
        perf = await (await client.get("/debug/perf")).json()
        assert perf["early_handovers"] == value
        return value

    async def fn(client):
        eng = server.engine
        sink, calls = eng.output_sink, []
        assert sink is not None  # the async worker set it at start
        eng.output_sink = lambda outs: (calls.append(
            (eng.clock._phase, len(outs))), sink(outs))[1]
        try:
            before = await read(client)
            for i, stream in enumerate((True, False, True)):
                r = await client.post("/v1/completions", json={
                    "model": "tiny-llama", "prompt": PROMPTS[i % 2],
                    "max_tokens": 5, "temperature": 0, "ignore_eos": True,
                    "stream": stream})
                assert r.status == 200
                await r.text()
            after = await read(client)
        finally:
            eng.output_sink = sink
        # one request at a time: its ragged step, then decode steps
        assert after - before == len(calls) == 3
        assert all(phase == "deliver" and n > 0 for phase, n in calls)

    asyncio.run(_with_client(server, fn))


def test_deliver_entered_twice_in_a_step_is_one_phase_and_loses_no_time():
    # the clock reads a fake ``time`` that moves only when the test says
    # so: the sums below are then StepClock's own arithmetic, not how the
    # machine scheduled this worker between two stamps
    fake = types.SimpleNamespace(now=100.0)
    fake.monotonic = lambda: fake.now
    fake.thread_time = lambda: fake.now / 2  # on the CPU half the time

    def sleep(seconds):
        fake.now += seconds

    with mock.patch.object(tracing, "time", fake):
        clock = StepClock()
        t0 = fake.monotonic()
        clock.begin_step()
        clock.describe("decode", rows=1, tokens=1)
        for phase in ("schedule", "build", "snapshot", "commit"):
            clock.enter(phase)
            sleep(0.001)
        clock.launch()
        clock.enter("postprocess")
        clock.enter("deliver")      # the hand-over, mid-step
        sleep(0.01)
        clock.enter("wait")
        sleep(0.01)
        clock.enter("postprocess")
        clock.enter("deliver")      # what step() returned
        sleep(0.01)
        seconds = clock.end_step()
        wall = fake.monotonic() - t0
    by = clock.seconds["decode"]
    assert by["deliver"][0] >= 0.02 and by["wait"][0] >= 0.01
    assert by["deliver"] == pytest.approx([0.02, 0.01])
    # (begin_step to the first phase is in no phase: microseconds)
    assert seconds == pytest.approx(sum(w for w, _ in by.values()), abs=1e-3)
    assert seconds <= wall and wall - seconds < 0.005
    assert seconds == pytest.approx(0.034)
    assert clock.steps == {"decode": 1, "ragged": 0, "prefill": 0, "other": 0}


def test_flight_record_names_its_steps_and_the_first_chunk(server):
    async def fn(client):
        for rid, stream in (("trace-stream", True), ("trace-plain", False)):
            r = await client.post("/v1/completions", json={
                "model": "tiny-llama", "prompt": PROMPTS[0], "max_tokens": 6,
                "temperature": 0, "ignore_eos": True, "stream": stream},
                headers={"x-request-id": rid})
            assert r.status == 200
            await r.text()
        recs = {x["client_request_id"]: x for x in (await (
            await client.get("/debug/requests")).json())["requests"]}
        for rid in ("trace-stream", "trace-plain"):
            steps = recs[rid]["steps"]
            assert 0 < steps["admitted"] <= steps["first_token"] \
                <= steps["last_token"] <= server.engine.clock.step_num
        tl = recs["trace-stream"]["timeline"]
        assert tl["first_token"] <= tl["first_chunk_written"] <= tl["finished"]
        assert "first_chunk_written" not in recs["trace-plain"]["timeline"]

    asyncio.run(_with_client(server, fn))


@pytest.mark.parametrize("body,level", [({}, 0),
                                        ({"python_tracer": False}, 0),
                                        ({"python_tracer": True}, 1)])
def test_debug_profile_python_tracer_level(server, monkeypatch, body, level):
    seen = {}

    def start_trace(log_dir, *a, profiler_options=None, **kw):
        seen["level"] = profiler_options.python_tracer_level

    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)

    async def fn(client):
        r = await client.post("/debug/profile",
                              json={"duration_ms": 1, **body})
        assert r.status == 200
        assert r.content_type == "application/gzip"

    asyncio.run(_with_client(server, fn))
    assert seen == {"level": level}


# -- names --------------------------------------------------------------------

@pytest.fixture(scope="module")
def ring_runner():
    cfg = make_config()
    cfg = EngineConfig(model=cfg.model, cache=cfg.cache,
                       scheduler=cfg.scheduler,
                       mesh=MeshConfig(data=1, tensor=1, seq=2),
                       attention_impl="ragged")
    return model_runner.ModelRunner(cfg, build_mesh(cfg.mesh),
                                    num_blocks=64)


@pytest.mark.parametrize("attr,name", [
    ("_ragged", "ragged_step"),
    ("_decode_multi", "decode_multi_step"),
    ("_prefill", "prefill_step"),
    ("_prefill_ring", "prefill_ring_step"),
])
def test_jitted_programs_carry_their_names(ring_runner, attr, name):
    assert getattr(ring_runner, attr).__name__ == name


def test_named_partial_names_the_compiled_module():
    def _toy_step(scale, x):
        return x * scale

    part = model_runner._named_partial(_toy_step, 2.0)
    assert isinstance(part, functools.partial)
    x = jnp.ones((4,), jnp.float32)
    text = jax.jit(part).lower(x).compile().as_text()
    assert "HloModule jit_toy_step" in text
    bare = jax.jit(functools.partial(_toy_step, 2.0)).lower(x).compile()
    assert "HloModule jit__unknown" in bare.as_text()


def test_ragged_attn_walk_counters_follow_the_dispatched_spans():
    """vllm:ragged_attn_walks_total / ..._narrow_walks_total on /metrics
    and the same two on /debug/perf: one walk per live span and 64-token
    tile of the stream, narrow when its rows fit the kernel's row block."""
    from production_stack_tpu.ops.ragged_paged_attention_pallas import (
        ROW_BLOCK,
    )
    names = ("vllm:ragged_attn_walks_total",
             "vllm:ragged_attn_narrow_walks_total")

    async def read(client):
        text = await (await client.get("/metrics")).text()
        values = [sum(_samples(text, n).values()) for n in names]
        perf = await (await client.get("/debug/perf")).json()
        assert [perf["ragged_attn_walks"],
                perf["ragged_attn_narrow_walks"]] == values
        return values

    server = EngineServer(make_config(attention_impl="ragged"))

    async def fn(client):
        eng = server.engine
        G = eng.config.model.q_per_kv
        before = await read(client)
        dispatches = eng.ragged_dispatches
        for prompt in ("hi", "x" * 40):  # 2 and 40 byte tokens (+ BOS)
            r = await client.post("/v1/completions", json={
                "model": "tiny-llama", "prompt": prompt, "max_tokens": 3,
                "temperature": 0, "ignore_eos": True})
            assert r.status == 200
        after = await read(client)
        # one request at a time: one ragged step of one span each
        assert eng.ragged_dispatches - dispatches == 2
        assert after[0] - before[0] == 2
        spans = [len(eng.tokenizer.encode(p)) for p in
                 ("hi", "x" * 40)]
        assert after[1] - before[1] == sum(
            n * G <= ROW_BLOCK for n in spans) == 1

    asyncio.run(_with_client(server, fn))


def test_ragged_attn_window_counters_follow_the_dispatched_spans():
    """vllm:ragged_attn_windows_total / ..._interior_windows_total on
    /metrics and the same two on /debug/perf, there from start-up: the
    32-token context windows (8 blocks of 4) the kernel's walks stream,
    and those of a span owning a whole 64-token tile that end at or below
    its first token's position."""
    names = ("vllm:ragged_attn_windows_total",
             "vllm:ragged_attn_interior_windows_total")

    async def read(client):
        text = await (await client.get("/metrics")).text()
        samples = [_samples(text, n) for n in names]
        assert all(samples), "both series exported, moved or not"
        values = [sum(s.values()) for s in samples]
        perf = await (await client.get("/debug/perf")).json()
        assert [perf["ragged_attn_windows"],
                perf["ragged_attn_interior_windows"]] == values
        return values

    server = EngineServer(make_config(attention_impl="ragged"))

    async def fn(client):
        eng = server.engine
        assert await read(client) == [0, 0]
        # 3 tokens (with BOS): one window, cut by the diagonal
        # 150 tokens: chunks of 64, 64 and 22 at the 64-token budget. The
        # first owns its tile at position 0 (2 windows, none interior),
        # the second at position 64 (4 windows to its reach of 128, the 2
        # below position 64 interior), the tail owns 22 rows of its tile
        # (5 windows to 150, none interior: not a whole tile)
        want = {"hi": (1, [1, 0]), "x" * 149: (3, [2 + 4 + 5, 2])}
        for prompt, (steps, delta) in want.items():
            before, dispatches = await read(client), eng.ragged_dispatches
            r = await client.post("/v1/completions", json={
                "model": "tiny-llama", "prompt": prompt, "max_tokens": 1,
                "temperature": 0, "ignore_eos": True})
            assert r.status == 200
            after = await read(client)
            assert eng.ragged_dispatches - dispatches == steps
            assert [a - b for a, b in zip(after, before)] == delta

    asyncio.run(_with_client(server, fn))


def test_decode_attn_call_counters_follow_the_decode_dispatches():
    """vllm:decode_attn_calls_total / ..._slab_calls_total on /metrics and
    the same two on /debug/perf: fused iterations x cache layers a decode
    dispatch, and those again where the runner says its geometry takes the
    decode kernel's slab body (never on the CPU: no Pallas kernel runs).
    Both series are there from start-up, so a ratio of their deltas reads
    0.0 and not nothing where the slab count stands still."""
    names = ("vllm:decode_attn_calls_total",
             "vllm:decode_attn_slab_calls_total")

    async def read(client):
        text = await (await client.get("/metrics")).text()
        samples = [_samples(text, n) for n in names]
        assert all(samples), "both series exported, moved or not"
        values = [sum(s.values()) for s in samples]
        perf = await (await client.get("/debug/perf")).json()
        assert [perf["decode_attn_calls"],
                perf["decode_attn_slab_calls"]] == values
        return values

    server = EngineServer(make_config(attention_impl="ragged"))

    async def fn(client):
        eng = server.engine
        assert eng.runner.decode_attn_slab is False
        per_dispatch = (max(eng.config.scheduler.multi_step, 1)
                        * eng.config.model.cache_layers)

        async def generate():
            dispatches = eng.decode_dispatches
            r = await client.post("/v1/completions", json={
                "model": "tiny-llama", "prompt": "hi", "max_tokens": 6,
                "temperature": 0, "ignore_eos": True})
            assert r.status == 200
            made = eng.decode_dispatches - dispatches
            assert made > 0
            return made * per_dispatch

        before = await read(client)
        calls = await generate()
        after = await read(client)
        assert [after[0] - before[0], after[1] - before[1]] == [calls, 0]
        # a runner whose geometry meets the kernel's predicate
        eng.runner.decode_attn_slab = True
        calls = await generate()
        last = await read(client)
        assert [last[0] - after[0], last[1] - after[1]] == [calls, calls]

    asyncio.run(_with_client(server, fn))
