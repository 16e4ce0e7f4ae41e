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
# seed 0, 12 tokens, ignore_eos (computed from an unpacked `git archive`
# of the parent, on the CPU)
PARENT_TOKENS = [
    [263, 351, 351, 351, 358, 351, 351, 351, 351, 351, 263, 331],
    [218, 400, 218, 400, 218, 400, 430, 36, 319, 218, 400, 218],
]
GREEDY = SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True)
# the phases of the worker loop outside a step: taking what arrived, the
# note of the step just ended, and (a step driven by hand) no phase at all
BETWEEN_STEPS = ("intake", "observe", "other")
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

@pytest.mark.parametrize("order", ["prepared", "in_order"])
def test_phases_sum_to_the_worker_wall_time_and_each_step_has_one_kind(order):
    eng = LLMEngine(make_config())
    clock = eng.clock
    seen = []
    # the phases in the order the thread entered them
    phases = []
    real_enter = clock.enter
    clock.enter = lambda phase, **kw: (phases.append(phase),
                                       real_enter(phase, **kw))[1]

    async def fn():
        ae = AsyncEngine(eng)
        ae.step_observer = seen.append
        t0 = time.monotonic()
        await ae.start()
        if order == "in_order":  # every prepared decode step is dropped
            eng.arrival_probe = lambda: True
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
    # a decode step waits for the program before it with its own inputs
    # committed, and launches a lead before the landing it foresees (the
    # worker gave the engine its wait: `launch` falls inside the wait) or
    # straight after the landing: between the wait's end and the launch
    # the thread does nothing but decide (`postprocess`: the probe, one
    # compare)
    phases = [p for i, p in enumerate(phases) if p != phases[i - 1] or not i]
    waits = [i for i, p in enumerate(phases) if p == "wait"]
    prepared = [i for i in waits if phases[i - 1] == "commit"]
    assert len(prepared) >= eng.decode_prepared_launches
    ahead = [i for i in prepared
             if phases[i + 1:i + 4] == ["launch", "postprocess", "wait"]]
    assert len(ahead) == eng.decode_ahead_launches
    launched = ahead + [i for i in prepared
                        if phases[i + 1:i + 3] == ["postprocess", "launch"]]
    assert len(launched) == eng.decode_prepared_launches
    assert (len(launched) >= 16) == (order == "prepared")
    assert bool(ahead) == (order == "prepared")
    if order == "in_order":
        assert not launched and len(prepared) >= 10
    # the worker's whole life is in some phase: host + wait + idle = wall
    assert _clock_total(clock) == pytest.approx(wall, rel=0.02)
    assert clock.idle_seconds > 0.2
    # every step was charged to exactly one kind, and only steps were
    assert sum(clock.steps.values()) == clock.step_num == step_count
    assert clock.steps["decode"] > 0
    assert clock.steps["ragged"] > 0
    assert not clock.in_step
    for kind in STEP_KINDS:
        if not clock.steps[kind]:
            # a kind that never ran holds no step phase (idle iterations
            # flush what lies between steps under "other")
            assert all(w == 0.0 for p, (w, _) in clock.seconds[kind].items()
                       if p not in BETWEEN_STEPS), kind
    decode = clock.seconds["decode"]
    for phase in ("schedule", "build", "snapshot", "commit", "launch",
                  "postprocess", "wait"):
        assert decode[phase][0] > 0.0, phase
    # on-CPU time is part of wall time (summed: a coarse thread clock may
    # charge a tick to a phase shorter than the tick)
    pairs = [v for by_phase in clock.seconds.values()
             for v in by_phase.values()]
    assert 0 < sum(c for _, c in pairs) <= sum(w for w, _ in pairs) + 0.05
    # the step histogram's observer got one positive duration per step,
    # and together they are the steps' phases (not idle, nor what lies
    # between two steps)
    assert len(seen) == step_count and min(seen) > 0
    in_steps = sum(w for by_phase in clock.seconds.values()
                   for p, (w, _) in by_phase.items()
                   if p not in BETWEEN_STEPS)
    assert sum(seen) == pytest.approx(in_steps, rel=0.02)


def test_a_step_driven_directly_opens_and_closes_its_own_step():
    eng = LLMEngine(make_config())
    outs = eng.generate(PROMPTS, GREEDY)
    assert list(outs.values()) == PARENT_TOKENS
    clock = eng.clock
    assert not clock.in_step and clock.step_num == sum(clock.steps.values())
    assert clock.steps["ragged"] > 0 and clock.steps["decode"] > 0
    assert eng.ragged_dispatches == clock.steps["ragged"]
    # driven by hand nothing arrives at a landing: every decode step
    # launches, all but the first after a ragged step prepared
    assert eng.decode_dispatches == clock.steps["decode"]
    assert eng.decode_prepared_launches == (
        eng.decode_dispatches - eng.ragged_dispatches)
    snap = clock.snapshot()
    assert set(snap["seconds"]) == set(STEP_KINDS)
    assert set(snap["seconds"]["decode"]) == {*HOST_PHASES, "wait"}


def test_perf_accountant_receives_the_clocks_seconds(monkeypatch):
    record, kind = "record_ragged", "ragged"
    eng = LLMEngine(make_config())
    calls = {"record_decode": [], "record_ragged": []}
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


def test_prepared_launches_counter_counts_each_one(server):
    """vllm:decode_prepared_launches_total beside
    vllm:decode_dispatches_total on /metrics, and the same two numbers in
    /debug/perf's `step_loop`: plain counts of the engine's launches."""
    names = ("vllm:decode_dispatches_total",
             "vllm:decode_prepared_launches_total")

    async def read(client):
        text = await (await client.get("/metrics")).text()
        values = [next(iter(_samples(text, n).values())) for n in names]
        loop = (await (await client.get("/debug/perf")).json())["step_loop"]
        assert [loop["decode_dispatches"],
                loop["decode_prepared_launches"]] == values
        return values

    async def fn(client):
        eng = server.engine
        assert eng.arrival_probe is not None  # the async worker set it
        launches = []
        real = eng.runner.prepare_decode

        def prepare(*a, **kw):
            launch = real(*a, **kw)
            return lambda tok=None: (launches.append(tok is not None),
                                     launch(tok))[1]

        eng.runner.prepare_decode = prepare
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
            eng.runner.prepare_decode = real
        # one request at a time: its ragged step, then four decode steps,
        # the first from the host's tokens
        assert [a - b for a, b in zip(after, before)] == [
            len(launches), sum(launches)] == [12, 9]

    asyncio.run(_with_client(server, fn))


def test_a_phase_entered_twice_in_a_step_is_one_phase_and_loses_no_time():
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
        clock.wait("decode")        # for the program before, inputs ready
        sleep(0.01)
        clock.enter("postprocess")  # the landing: launch, or an arrival?
        sleep(0.01)
        clock.launch()
        clock.enter("postprocess")  # the landed step's tokens
        sleep(0.01)
        seconds = clock.end_step()
        wall = fake.monotonic() - t0
    by = clock.seconds["decode"]
    assert by["postprocess"][0] >= 0.02 and by["wait"][0] >= 0.01
    assert by["postprocess"] == pytest.approx([0.02, 0.01])
    assert clock.last_wait == "decode"
    # a step opens in its first phase and drops nothing: its seconds are
    # its phases' and the clock's own stamps, begin to end
    assert seconds == pytest.approx(sum(w for w, _ in by.values()), abs=1e-12)
    assert seconds == pytest.approx(wall, abs=1e-12)
    assert seconds == pytest.approx(0.034)
    assert clock.steps == {"decode": 1, "ragged": 0, "other": 0}


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
def runner():
    cfg = make_config()
    return model_runner.ModelRunner(cfg, build_mesh(cfg.mesh),
                                    num_blocks=64)


@pytest.mark.parametrize("attr,name", [
    ("_ragged", "ragged_step"),
    ("_decode_multi", "decode_multi_step"),
])
def test_jitted_programs_carry_their_names(runner, attr, name):
    assert getattr(runner, attr).__name__ == name


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

    server = EngineServer(make_config())

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

    server = EngineServer(make_config())

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

    server = EngineServer(make_config())

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


# -- a request's time to first token, in parts --------------------------------
# On a clock the test moves (every reading is a millisecond after the one
# before, so no two stamps are equal) and with step() driven by hand: what
# is held below is the stamps' order and arithmetic, not a machine's speed.

def _fake_time(tick: float = 0.001):
    fake = types.SimpleNamespace(now=1000.0)

    def monotonic():
        fake.now += tick
        return fake.now
    fake.monotonic = monotonic
    fake.time = lambda: 1.7e9 + fake.monotonic()
    fake.thread_time = lambda: fake.now / 2
    return fake


@pytest.fixture
def fake_time():
    from production_stack_tpu import flight_recorder

    fake = _fake_time()
    with mock.patch.object(tracing, "time", fake), \
            mock.patch.object(flight_recorder, "time", fake):
        yield fake


def _serve_by_hand(server, eng, root, prompts, streamed=True):
    """What the server does for one request of ``len(prompts)`` choices,
    with the engine stepped by hand: open the record, enqueue the adds,
    step until every choice has finished, stamp the first chunk, close
    the parts. Returns the record."""
    clock = eng.clock
    rec = server.flight_recorder.begin(
        request_id=root, num_prompt_tokens=0, num_output_tokens=0,
        steps={"received": clock.step_num})
    server._inflight[root] = rec
    enqueued = (clock.now(), clock.step_num)
    live = set()
    for i, ids in enumerate(prompts):
        eng.add_request(f"{root}-{i}", prompt_token_ids=ids,
                        sampling=dataclasses.replace(GREEDY, max_tokens=3),
                        enqueued=enqueued)
        live.add(f"{root}-{i}")
    try:
        while live:
            for out in eng.step():
                if out.finished and out.request_id in live:
                    live.discard(out.request_id)
                    server._observe_finished(root, out)
        if streamed:
            server.flight_recorder.stamp(rec, "first_chunk_written")
        server._note_ttft_parts(rec)
    finally:
        server._inflight.pop(root, None)
    return rec


@pytest.mark.parametrize("case,choices,prompt_len,slots,dispatches", [
    ("single", 1, 9, 4, 1),
    ("two_choices", 2, 9, 4, 1),
    ("admitted_a_step_late", 1, 9, 1, 1),
    ("three_dispatches", 1, 150, 4, 3),
    ("not_streamed", 1, 9, 4, 1),
])
def test_ttft_parts_telescope(server, fake_time, case, choices, prompt_len,
                              slots, dispatches):
    cfg = make_config()
    eng = LLMEngine(dataclasses.replace(cfg, scheduler=dataclasses.replace(
        cfg.scheduler, max_num_seqs=slots)))
    if case == "admitted_a_step_late":
        # the one slot is taken: the request waits in the queue for it
        eng.add_request("blocker", prompt_token_ids=[5, 6, 7],
                        sampling=dataclasses.replace(GREEDY, max_tokens=4))
        eng.step()
    parts0 = dict(server.ttft_parts.count)
    rec = _serve_by_hand(
        server, eng, f"root-{case}",
        [[7 + (j + k) % 50 for j in range(prompt_len)]
         for k in range(choices)],
        streamed=case != "not_streamed")
    tl, steps, parts = rec["timeline"], rec["steps"], rec["ttft_parts"]
    stamps = [s for s in tracing.TTFT_STAMPS if s in tl]
    assert stamps == list(tracing.TTFT_STAMPS[:len(stamps)])
    assert [tl[a] < tl[b] for a, b in zip(stamps, stamps[1:])] == \
        [True] * (len(stamps) - 1)
    assert list(parts) == list(tracing.TTFT_PARTS[:len(stamps) - 1])
    assert sum(parts.values()) == pytest.approx(
        tl[stamps[-1]] - tl["received"], abs=1e-9)
    if case == "not_streamed":
        assert stamps[-1] == "first_token"
    else:
        assert stamps[-1] == "first_chunk_written"
    # every stamp the engine thread took names its engine step, in order
    named = [steps[k] for k in ("received", "enqueued", "arrival", "admitted",
                                "first_launch", "first_token", "last_token")]
    assert named == sorted(named) and steps["first_launch"] >= 1
    # (`arrival` names the step begun last before the intake: the step
    # after it is the first that can admit)
    assert (steps["admitted"] > steps["arrival"] + 1) == (
        case == "admitted_a_step_late")
    assert rec["prefill_dispatches"] == dispatches
    assert steps["first_token"] - steps["first_launch"] >= dispatches - 1
    # the same parts feed the totals (/debug/perf, /metrics), once a request
    for part in parts:
        assert server.ttft_parts.count[part] == parts0[part] + 1


@pytest.mark.parametrize("steps_before,kind,waited", [
    (0, "idle", "idle"),
    (1, "ragged", "none"),      # the ragged step launched and waited for nothing
    (2, "decode", "ragged"),    # the step after it waited the prompt's program out
    (3, "decode", "decode")])
def test_arrival_carries_what_the_step_before_its_intake_waited_for(
        fake_time, steps_before, kind, waited):
    eng = LLMEngine(make_config())
    eng.add_request("first", prompt_token_ids=[5, 6, 7], sampling=GREEDY)
    for _ in range(steps_before):
        eng.step()
    assert (eng.clock.last_kind, eng.clock.last_wait) == (kind, waited)
    seq = eng.add_request("probe", prompt_token_ids=[8, 9], sampling=GREEDY)
    assert seq.arrival_after == waited
    assert seq.arrival_step == eng.clock.step_num == steps_before
    # a thread that has waited for work since its last step was idle
    eng.clock.idle()
    late = eng.add_request("late", prompt_token_ids=[8, 9], sampling=GREEDY)
    assert late.arrival_after == "idle"


class _Loop:
    """call_later of an event loop, fired by hand."""

    def __init__(self):
        self.calls = []

    def call_later(self, delay, fn):
        self.calls.append((delay, fn))
        return types.SimpleNamespace(cancel=lambda: None)


@pytest.mark.parametrize("due_in,late_by,during", [
    (0.5, 0.0, "launch"),     # on time, in a host phase
    (1.2, 21.0, "wait"),      # due in the wait, run after it ended
    (0.9, 22.0, "launch"),    # due just before the wait began
    (21.5, 1.5, "postprocess"),
    (-50.0, 80.0, "none"),    # due before the switches the clock keeps
])
def test_a_late_heartbeat_is_charged_to_the_phase_at_its_due_instant(
        due_in, late_by, during):
    """Milliseconds: the step launches at 0, waits from 1 to 21, then
    postprocesses; the beat is due ``due_in`` and runs ``late_by`` late."""
    fake = types.SimpleNamespace(now=50.0)
    fake.monotonic = lambda: fake.now
    fake.thread_time = lambda: fake.now / 4   # on the CPU a quarter of it
    draws = types.SimpleNamespace(uniform=lambda a, b: 1.0)
    with mock.patch.object(tracing, "time", fake), \
            mock.patch.object(tracing, "random", draws):
        clock = StepClock()
        assert clock.phase_at(fake.now) == "none"   # never started
        t0 = fake.now
        clock.begin_step()
        clock.launch()
        fake.now = t0 + 0.001
        clock.enter("wait")
        fake.now = t0 + 0.021
        clock.enter("postprocess")
        lag, loop = tracing.LoopLag(clock), _Loop()
        fake.now = t0 + due_in / 1e3 - lag.PERIOD
        lag.start(loop)
        (delay, wake), = loop.calls
        assert delay == lag.PERIOD
        fake.now = t0 + (due_in + late_by) / 1e3
        wake()
        assert lag.ticks == 1 and len(loop.calls) == 2
        assert lag.lag_seconds.get(during, 0.0) == pytest.approx(
            late_by / 1e3, abs=1e-9)
        assert sum(lag.lag_seconds.values()) == pytest.approx(late_by / 1e3,
                                                              abs=1e-9)
        # re-armed from the wake, not from the beat it missed
        assert lag._due == pytest.approx(fake.now + lag.PERIOD)
        assert lag.take_max() == pytest.approx(late_by / 1e3, abs=1e-9)
        assert lag.take_max() == 0.0
        # the loop thread's own clocks, start to this wake
        assert lag.wall_seconds == pytest.approx(
            lag.PERIOD + late_by / 1e3, abs=1e-9)
        assert lag.cpu_seconds == pytest.approx(lag.wall_seconds / 4)


def test_heartbeat_delays_are_drawn_around_the_period():
    """Half to one and a half periods after each wake, so that due
    instants do not fall on a lattice behind a step's end."""
    lag, loop = tracing.LoopLag(StepClock()), _Loop()
    lag.start(loop)
    for _ in range(200):
        loop.calls[-1][1]()     # the wake re-arms
    delays = [d for d, _ in loop.calls]
    assert lag.ticks == 200 and len(delays) == 201
    assert 0.5 * lag.PERIOD <= min(delays) < 0.6 * lag.PERIOD
    assert 1.4 * lag.PERIOD < max(delays) <= 1.5 * lag.PERIOD
    assert sum(delays) / len(delays) == pytest.approx(lag.PERIOD, rel=0.1)
    lag.stop()


def _clocked_step(clock, fake, kind, phases, compile_inside=False):
    clock.begin_step()
    clock.describe(kind, rows=2, tokens=2)
    for phase, seconds in phases:
        if phase == "launch":
            clock.launch()
        elif phase != "schedule":
            clock.enter(phase)
        fake.now += seconds
    if compile_inside:
        clock.compiles += 1
    return clock.end_step()


NORMAL = (("schedule", 0.001), ("launch", 0.001), ("wait", 0.020),
          ("postprocess", 0.002))


@pytest.mark.parametrize("overrun,compile_inside,cause", [
    ("wait", False, "wait"), ("postprocess", False, "postprocess"),
    ("schedule", False, "schedule"), ("wait", True, "compile")])
def test_a_step_at_three_times_its_kinds_reference_is_slow_and_names_its_cause(
        overrun, compile_inside, cause):
    fake = types.SimpleNamespace(now=10.0)
    fake.monotonic = lambda: fake.now
    fake.thread_time = lambda: fake.now
    # three times a normal step's 24 ms, all of the excess in one phase
    stalled = tuple((p, s + 0.048 if p == overrun else s) for p, s in NORMAL)
    with mock.patch.object(tracing, "time", fake):
        clock = StepClock()
        # among the first 32 of a kind nothing is slow, whatever it took
        # (the first of all follows no step: a reference of its own)
        for i in range(tracing.SLOW_WINDOW + 1):
            _clocked_step(clock, fake, "decode",
                          stalled if i in (0, 7, 31) else NORMAL)
        _clocked_step(clock, fake, "decode", NORMAL)
        assert not clock.slow_steps
        seconds = _clocked_step(clock, fake, "decode", stalled,
                                compile_inside)
        assert seconds == pytest.approx(0.072)
        (slow,) = clock.slow_steps
        assert slow["cause"] == cause and slow["kind"] == "decode"
        assert slow["step"] == clock.step_num
        assert (slow["rows"], slow["tokens"]) == (2, 2)
        assert slow["seconds"] == pytest.approx(0.072)
        assert slow["reference"] == pytest.approx(0.024)
        assert slow["phases"][overrun] == pytest.approx(
            dict(NORMAL)[overrun] + 0.048)
        assert sum(slow["phases"].values()) == pytest.approx(0.072)
        assert clock.slow_snapshot()["seconds"]["decode"][cause] == \
            pytest.approx(0.072)
        assert slow["after"] == "decode"
        # just under twice the reference is not slow
        _clocked_step(clock, fake, "decode",
                      tuple((p, 1.9 * s) for p, s in NORMAL))
        assert len(clock.slow_steps) == 1
        # a step waits out the dispatch before it: a decode step after a
        # ragged step is held against its own like, not against these
        _clocked_step(clock, fake, "ragged", stalled)
        _clocked_step(clock, fake, "decode", stalled)
        assert len(clock.slow_steps) == 1
        assert sum(clock.slow_seconds["ragged"].values()) == 0.0


def test_no_moment_of_the_worker_loop_is_dropped_with_an_observer_set():
    """host + wait + idle is the thread's time by the clock's own stamps,
    first switch to last flush: the observer's call, the hole between two
    steps and the time before a step's first phase are all in a phase."""
    eng = LLMEngine(make_config())
    clock, seen = eng.clock, []

    async def fn():
        ae = AsyncEngine(eng)
        ae.step_observer = seen.append
        await ae.start()
        toks = []
        async for out in ae.generate(eng.tokenizer.encode(PROMPTS[0]),
                                     GREEDY):
            toks.extend(out.new_token_ids)
        ae.stop()
        return toks

    with mock.patch.object(tracing, "time", _fake_time(0.0005)):
        assert asyncio.run(fn()) == PARENT_TOKENS[0]
    assert len(seen) == clock.step_num > 0
    assert clock.flushed_at > clock.started_at > 0
    assert _clock_total(clock) == pytest.approx(
        clock.flushed_at - clock.started_at, abs=1e-9)
    observed = sum(by["observe"][0] for by in clock.seconds.values())
    assert observed > 0
    snap = clock.snapshot()
    assert (snap["started_at"], snap["flushed_at"]) == (
        clock.started_at, clock.flushed_at)


def test_the_server_exports_parts_lag_slow_steps_and_the_routers_stamp(server):
    families = ("vllm:request_ttft_part_seconds_total",
                "vllm:request_ttft_parts_total",
                "vllm:server_loop_lag_seconds_total",
                "vllm:server_loop_lag_in_wait_seconds_total",
                "vllm:server_loop_ticks_total",
                "vllm:server_loop_wall_seconds_total",
                "vllm:server_loop_cpu_seconds_total",
                "vllm:server_loop_lag_max_seconds",
                "vllm:engine_slow_step_seconds_total")

    async def fn(client):
        first = await (await client.get("/metrics")).text()
        for fam in families:   # there from the first scrape, at 0 or more
            assert _samples(first, fam), fam
        sent = time.time()
        for rid, stream, hdr in (("parts-stream", True, repr(sent)),
                                 ("parts-plain", False, None),
                                 ("parts-bad", True, "soon")):
            r = await client.post("/v1/completions", json={
                "model": "tiny-llama", "prompt": PROMPTS[1], "max_tokens": 4,
                "temperature": 0, "ignore_eos": True, "stream": stream},
                headers={"x-request-id": rid, **(
                    {"x-router-sent-unix": hdr} if hdr else {})})
            assert r.status == 200
            await r.text()
        recs = {x["client_request_id"]: x for x in (await (
            await client.get("/debug/requests")).json())["requests"]}
        rec = recs["parts-stream"]
        assert rec["router_sent_unix"] == sent <= rec["received_unix"]
        assert "router_sent_unix" not in recs["parts-plain"]
        assert "router_sent_unix" not in recs["parts-bad"]
        tl, parts = rec["timeline"], rec["ttft_parts"]
        assert list(parts) == list(tracing.TTFT_PARTS)
        assert min(parts.values()) >= 0.0
        assert sum(parts.values()) == pytest.approx(
            tl["first_chunk_written"] - tl["received"], abs=1e-9)
        assert rec["intake_after"] in (*STEP_KINDS, "idle")
        assert rec["prefill_dispatches"] == 1
        assert "server_deliver" not in recs["parts-plain"]["ttft_parts"]
        perf = await (await client.get("/debug/perf")).json()
        second = await (await client.get("/metrics")).text()
        for part in tracing.TTFT_PARTS:
            got = perf["ttft_parts"][part]
            label = f'{{model_name="tiny-llama",part="{part}"}}'
            assert got["count"] == _samples(second, families[1])[
                families[1] + label] >= 2
            assert got["seconds"] == pytest.approx(_samples(
                second, families[0])[families[0] + label])
        lag = perf["loop_lag"]
        assert lag["period_seconds"] == 0.1 and lag["ticks"] > 0
        assert 0.0 <= lag["cpu_seconds"] <= lag["wall_seconds"] + 0.05
        assert set(lag["lag_seconds"]) <= {*HOST_PHASES, "wait", "idle",
                                          "none"}
        assert set(perf["slow_steps"]["seconds"]) == set(
            tracing.DISPATCH_KINDS)
        assert isinstance(perf["slow_steps"]["last"], list)

    asyncio.run(_with_client(server, fn))
