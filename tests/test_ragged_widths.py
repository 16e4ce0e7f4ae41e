"""A ragged step is as wide as what it carries (PR 42).

The ragged program exists at the scheduler's few stream widths
(``SchedulerConfig.ragged_stream_widths``: the token budget and, where it
pays, one narrow width) and each step runs at the narrowest that holds the
tokens the scheduler gave it:

a. the width rule, a function of ``max_num_batched_tokens`` and
   ``max_num_seqs`` alone;
b. ``_run_ragged`` picks the first width that holds the packed tokens, and
   what the scheduler decides is what it decided with one width;
c. the narrow and the wide program are the same computation: a dense, an
   MoE, a looped and a hybrid model serve the same tokens and
   log-probabilities at either;
d. ``warmup()`` compiles every signature at every width;
e. the counter, the launch annotation's ``width`` and the slow-step
   reference by width.
"""

import asyncio
import dataclasses
import glob
import json
import os
import types
from unittest import mock

import jax
import numpy as np
import pytest

from production_stack_tpu.engine import tracing
from production_stack_tpu.engine.config import (
    STREAM_WIDTH_ALIGN,
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.engine.tracing import StepClock
from production_stack_tpu.engine.weights import init_or_load
from production_stack_tpu.ops.ragged_paged_attention_pallas import (
    Q_TILE,
    q_tile_for,
)
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the smallest configuration with a narrow width: (128, 512)
BUDGET, SLOTS, NARROW = 512, 4, 128
# the end-to-end parity tests' tolerance (tests/test_ragged_attention.py)
LOGPROB_ABS = 1e-3


def widths(budget, slots):
    return SchedulerConfig(max_num_seqs=slots,
                           max_num_batched_tokens=budget).ragged_stream_widths


# -- a. the rule --------------------------------------------------------------

@pytest.mark.parametrize("budget,slots,want", [
    (2048, 64, (512, 2048)),     # every cell of the benchmark
    (2048, 256, (512, 2048)),    # two rows a slot still fit
    (2048, 257, (2048,)),        # under two rows a slot: the budget alone
    (4096, 64, (1024, 4096)),
    (1024, 64, (256, 1024)),
    (1000, 8, (128, 1000)),      # a quarter, cut to a whole tile
    (512, 4, (128, 512)),
    (512, 128, (512,)),
    (256, 8, (256,)),            # chipbench's tiny configurations
    (64, 4, (64,)), (32, 8, (32,)), (16, 4, (16,)), (4, 2, (4,)),
])
def test_the_widths_follow_from_budget_and_slots(budget, slots, want):
    assert widths(budget, slots) == want


def test_a_narrow_width_is_whole_tiles_holds_two_rows_a_slot_and_is_never_the_slots():
    assert STREAM_WIDTH_ALIGN == Q_TILE
    for budget in (*range(1, 70), 96, 128, 250, 256, 384, 511, 512, 513, 640,
                   1000, 1024, 2047, 2048, 2049, 3000, 4096, 8192, 16384):
        for slots in (1, 2, 4, 8, 12, 64, 128, 256, 512, 1024):
            w = widths(budget, slots)
            assert w[-1] == budget and list(w) == sorted(set(w))
            assert len(w) <= 2
            for narrow in w[:-1]:
                assert narrow != slots and narrow >= 2 * slots
                for group in (1, 2, 4, 8, 16):
                    assert narrow % q_tile_for(group) == 0
    cfg = SchedulerConfig()  # the defaults every cell runs
    assert (cfg.max_num_batched_tokens, cfg.max_num_seqs) == (2048, 64)
    assert cfg.ragged_stream_widths == (512, 2048)
    assert [cfg.stream_width_for(n) for n in (1, 511, 512, 513, 2048)] == [
        512, 512, 512, 2048, 2048]


def test_the_suites_tiny_manifests_keep_one_width():
    found = glob.glob(os.path.join(ROOT, "chipbench", "**", "manifest.json"),
                      recursive=True)
    tiny = [m for m in found if "tiny-" in m]
    assert len(tiny) >= 4
    for path in found:
        with open(path) as f:
            m = json.load(f)
        w = widths(m["token_budget"], m["decode_slots"])
        # a quarter of the budget and the budget: (512, 2048) for every
        # configuration but the one whose budget is 4096 (PR 59)
        assert w == ((m["token_budget"],) if path in tiny
                     else (m["token_budget"] // 4, m["token_budget"]))
        assert path in tiny or m["token_budget"] in (2048, 4096)


# -- engines ------------------------------------------------------------------

def one_device():
    return build_mesh(MeshConfig(), devices=jax.devices()[:1])


def dense_cfg() -> ModelConfig:
    return dataclasses.replace(ModelConfig.from_pretrained("tiny-llama"),
                               max_model_len=1024)


def hybrid_cfg() -> ModelConfig:
    # as tests/test_solar_open2.py builds one
    with open(os.path.join(ROOT, "chipbench", "tests", "configs",
                           "tiny-solar-open2", "config.json")) as f:
        hf = json.load(f)
    return dataclasses.replace(ModelConfig.from_hf_config(hf, "tiny-solar"),
                               dtype="float32")


MODELS = {
    "dense": dense_cfg,
    "moe": lambda: ModelConfig.from_pretrained("tiny-olmoe"),
    "looped": lambda: ModelConfig.from_pretrained("tiny-ouro"),
    "hybrid": hybrid_cfg,
}


@pytest.fixture(scope="module")
def weights():
    made = {}

    def get(family):
        if family not in made:
            cfg, mesh = MODELS[family](), one_device()
            made[family] = cfg, mesh, init_or_load(cfg, mesh, seed=3)
        return made[family]
    return get


def make_engine(cfg, mesh, params, budget=BUDGET, slots=SLOTS) -> LLMEngine:
    return LLMEngine(
        EngineConfig(model=cfg,
                     cache=CacheConfig(block_size=16, num_blocks=160),
                     scheduler=SchedulerConfig(
                         max_num_seqs=slots, max_num_batched_tokens=budget),
                     mesh=MeshConfig(data=1, tensor=1)),
        mesh=mesh, params=params)


def one_width(monkeypatch):
    """The parent's behaviour: the budget is the one width."""
    monkeypatch.setattr(
        SchedulerConfig, "ragged_stream_widths",
        property(lambda self: (self.max_num_batched_tokens,)))


def serve(eng, requests, arrive_at=()):
    """Drive ``requests`` ((id, prompt, sampling), ...) to their end, the
    first now and the others before the steps numbered ``arrive_at``. Returns
    ({id: (tokens, log-probabilities, top lists)}, what the scheduler
    decided step by step, the (width, live tokens) of each ragged step)."""
    decided, steps = [], []
    schedule, ragged_step = eng.scheduler.schedule, eng.runner.ragged_step

    def noting_schedule():
        out = schedule()
        decided.append((
            [s.request_id for s in out.decodes],
            [(sp.seq.request_id, sp.chunk_start, sp.chunk_len)
             for sp in out.prefills]))
        return out

    def noting_step(tokens, positions, *rest, **kw):
        assert tokens.shape == positions.shape == (1, len(rest[3]))
        steps.append((tokens.shape[1], int(rest[2][-1])))  # cu_q_lens' end
        return ragged_step(tokens, positions, *rest, **kw)

    queue = list(requests)
    out = {rid: ([], [], []) for rid, *_ in queue}

    def submit(rid, prompt, sampling):
        eng.add_request(rid, prompt_token_ids=list(prompt), sampling=sampling)

    due = [0, *arrive_at] if arrive_at else [0] * len(queue)
    with mock.patch.object(eng.scheduler, "schedule", noting_schedule), \
            mock.patch.object(eng.runner, "ragged_step", noting_step):
        n = 0
        while eng.has_unfinished() or queue:
            while queue and n >= due[len(requests) - len(queue)]:
                submit(*queue.pop(0))
            for o in eng.step():
                toks, lps, tops = out[o.request_id]
                toks += o.new_token_ids
                for lp, top in o.new_logprobs or ():
                    lps.append(lp)
                    tops.append(top)
            n += 1
            assert n < 500
    return out, decided, steps


def prompt(seed, n, vocab=200):
    return [int(t) for t in
            np.random.default_rng(seed).integers(1, vocab, n)]


def greedy(max_tokens=6, **kw):
    return SamplingParams(temperature=0.0, max_tokens=max_tokens,
                          ignore_eos=True, **kw)


# -- b. the selection ---------------------------------------------------------

def test_a_step_runs_at_the_first_width_that_holds_it(weights, monkeypatch):
    cfg, mesh, params = weights("dense")
    eng = make_engine(cfg, mesh, params)
    assert eng.config.scheduler.ragged_stream_widths == (NARROW, BUDGET)
    for i, (n, width) in enumerate([(NARROW, NARROW), (NARROW + 1, BUDGET),
                                    (BUDGET, BUDGET), (1, NARROW)]):
        narrow_before = eng.ragged_narrow_dispatches
        _, _, steps = serve(eng, [(f"r{i}", prompt(i, n), greedy(2))])
        assert steps == [(width, n)]  # then a decode-only step
        assert (eng.ragged_narrow_dispatches - narrow_before
                == (width == NARROW))
    # decode rows count: 3 rows + 125 tokens fill the narrow width, one
    # more token does not fit it
    for extra, width in ((NARROW - 3, NARROW), (NARROW - 2, BUDGET)):
        reqs = [(f"d{extra}-{j}", prompt(10 + j, 5), greedy(8))
                for j in range(3)]
        reqs.append((f"p{extra}", prompt(extra, extra), greedy(2)))
        _, _, steps = serve(eng, reqs, arrive_at=(0, 0, 3))
        assert steps[-1] == (width, 3 + extra)
    assert eng.ragged_dispatches == eng.stats()["ragged_dispatches_total"]
    assert (eng.stats()["ragged_narrow_dispatches_total"]
            == eng.ragged_narrow_dispatches)


REQUESTS = [
    ("a", prompt(1, 40), greedy(12, logprobs=3)),
    ("b", prompt(2, 300), greedy(5, logprobs=3)),
    ("c", prompt(3, 700), greedy(4, logprobs=3)),   # two chunks
    ("d", prompt(4, 9), SamplingParams(temperature=0.8, top_p=0.9, seed=11,
                                       max_tokens=7, ignore_eos=True,
                                       logprobs=3)),
    ("e", prompt(5, 130), greedy(3, logprobs=3)),
]
ARRIVE_AT = (1, 2, 4, 9)


def assert_same_answers(got, want):
    assert got.keys() == want.keys()
    for rid in want:
        (toks, lps, tops), (wtoks, wlps, wtops) = got[rid], want[rid]
        assert toks == wtoks, rid
        assert lps == pytest.approx(wlps, abs=LOGPROB_ABS)
        for top, wtop in zip(tops, wtops, strict=True):
            assert [t for t, _ in top] == [t for t, _ in wtop]
            assert [lp for _, lp in top] == pytest.approx(
                [lp for _, lp in wtop], abs=LOGPROB_ABS)


def test_the_scheduler_decides_what_it_decided_with_one_width(
        weights, monkeypatch):
    cfg, mesh, params = weights("dense")
    got, decided, steps = serve(make_engine(cfg, mesh, params), REQUESTS,
                                ARRIVE_AT)
    assert {w for w, _ in steps} == {NARROW, BUDGET}
    assert all(w == (NARROW if cu <= NARROW else BUDGET) for w, cu in steps)
    one_width(monkeypatch)
    want, decided_1, steps_1 = serve(make_engine(cfg, mesh, params),
                                     REQUESTS, ARRIVE_AT)
    assert {w for w, _ in steps_1} == {BUDGET}
    assert decided == decided_1
    assert [cu for _, cu in steps] == [cu for _, cu in steps_1]
    assert_same_answers(got, want)


# -- c. one computation at either width ---------------------------------------

@pytest.mark.parametrize("family", list(MODELS))
def test_the_narrow_and_the_wide_program_give_the_same_answers(
        weights, monkeypatch, family):
    """Two decode rows, one prefill span and an empty slot in one stream,
    through the program at 128 and at 512 wide."""
    cfg, mesh, params = weights(family)
    vocab = min(cfg.vocab_size, 200)
    reqs = [("d0", prompt(1, 21, vocab), greedy(9, logprobs=3)),
            ("d1", prompt(2, 33, vocab), greedy(9, logprobs=3)),
            ("p", prompt(3, 50, vocab), greedy(4, logprobs=3))]
    eng = make_engine(cfg, mesh, params)
    got, _, steps = serve(eng, reqs, arrive_at=(0, 3))
    # the mixed step: 2 decode rows + the 50-token span, slot 3 empty
    assert steps == [(NARROW, 54), (NARROW, 52)]
    assert eng.ragged_narrow_dispatches == eng.ragged_dispatches == 2
    one_width(monkeypatch)
    wide = make_engine(cfg, mesh, params)
    want, _, steps = serve(wide, reqs, arrive_at=(0, 3))
    assert steps == [(BUDGET, 54), (BUDGET, 52)]
    assert wide.ragged_narrow_dispatches == 0
    assert all(len(toks) == sp.max_tokens
               for (_, _, sp), (toks, _, _) in zip(reqs, got.values()))
    assert_same_answers(got, want)


# -- d. warm-up ---------------------------------------------------------------

def live_traffic(eng):
    """A narrow step, then a wide one: greedy, sampled, with logprobs,
    under a grammar and with token controls."""
    sampled = dict(temperature=0.7, max_tokens=3, ignore_eos=True)
    for i, feature in enumerate((
            {}, {"logprobs": 5}, {"guided_regex": "[ -~]*"},
            {"logit_bias": {1: 0.0}}, {"presence_penalty": 0.5})):
        for n in (12, NARROW + 40):
            reqs = [(f"g{i}-{n}", prompt(2 * n + i, n), greedy(3, **feature)),
                    (f"s{i}-{n}", prompt(3 * n + i, n),
                     SamplingParams(**sampled, **feature))]
            # warm-up compiles the penalised DECODE program greedy only
            for req in reqs[:1 if "presence_penalty" in feature else 2]:
                _, _, steps = serve(eng, [req])
                assert steps == [(NARROW if n <= NARROW else BUDGET, n)]


def builds_by_width(compile_counts: dict, kind: str = "ragged") -> dict:
    """{stream width: programs built} of one kind. A program's name is
    ``w<width>:<variant>`` (perf_accounting.program_bucket) and one name is
    one program, so every count is 1."""
    out: dict = {}
    for (k, bucket), n in compile_counts.items():
        if k == kind:
            assert n == 1, (bucket, n)
            width = int(bucket.split(":")[0][1:])
            out[width] = out.get(width, 0) + 1
    return out


def test_warmup_compiles_both_widths_of_every_variant(weights):
    cfg, mesh, params = weights("dense")
    eng = make_engine(cfg, mesh, params)
    eng.warmup()
    fields = eng.perf.stats_fields()
    assert fields["unexpected_recompiles"] == 0
    # {greedy, sampled} x {plain, grammar, controls}, once a width
    ragged = builds_by_width(fields["compile_counts"])
    assert sorted(ragged.values()) == [6, 6], fields["compile_counts"]
    narrow = min(ragged)
    assert {b.split(":")[1] for (k, b) in fields["compile_counts"]
            if k == "ragged" and b.startswith(f"w{narrow}:")} == {
        "greedy", "sampled", "greedy+grammar", "sampled+grammar",
        "greedy+controls", "sampled+controls"}
    assert 0 < eng.ragged_narrow_dispatches < eng.ragged_dispatches
    live_traffic(eng)
    fields = eng.perf.stats_fields()
    assert fields["unexpected_recompiles"] == 0, fields["compile_counts"]


def test_warmup_with_one_width_compiles_what_it_did(weights):
    """A tiny configuration: the budget alone, six ragged signatures."""
    cfg, mesh, params = weights("dense")
    eng = make_engine(cfg, mesh, params, budget=64)
    assert eng.config.scheduler.ragged_stream_widths == (64,)
    eng.warmup()
    fields = eng.perf.stats_fields()
    assert builds_by_width(fields["compile_counts"]) == {64: 6}
    assert eng.ragged_narrow_dispatches == 0 < eng.ragged_dispatches
    serve(eng, REQUESTS[:2], ARRIVE_AT[:1])
    assert eng.perf.stats_fields()["unexpected_recompiles"] == 0


# -- e. the counter, the annotation, the slow-step reference ------------------

def test_the_counter_is_exported_from_start_up_and_follows_the_steps():
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.server import EngineServer

    names = ("vllm:ragged_dispatches_total",
             "vllm:ragged_narrow_dispatches_total")
    server = EngineServer(EngineConfig(
        model=dense_cfg(), cache=CacheConfig(block_size=16, num_blocks=160),
        scheduler=SchedulerConfig(max_num_seqs=SLOTS,
                                  max_num_batched_tokens=BUDGET),
        mesh=MeshConfig(data=1, tensor=1)))

    async def read(client):
        text = await (await client.get("/metrics")).text()
        values = []
        for name in names:
            lines = [line for line in text.splitlines()
                     if line.split("{", 1)[0] == name]
            assert len(lines) == 1, name
            values.append(float(lines[0].rpartition(" ")[2]))
        perf = await (await client.get("/debug/perf")).json()
        assert [perf["ragged_dispatches"],
                perf["ragged_narrow_dispatches"]] == values
        return values

    async def fn():
        async with TestClient(TestServer(server.build_app())) as client:
            assert await read(client) == [0.0, 0.0]
            for text, want in (("hi", [1.0, 1.0]), ("x" * 200, [2.0, 1.0])):
                r = await client.post("/v1/completions", json={
                    "model": "tiny-llama", "prompt": text, "max_tokens": 2,
                    "temperature": 0, "ignore_eos": True})
                assert r.status == 200
                assert await read(client) == want

    asyncio.run(fn())


def test_launch_carries_the_width_of_a_ragged_step_only():
    seen = []

    class Annotation:
        def __init__(self, name, **attrs):
            self.name, self.attrs = name, attrs

        def __enter__(self):
            seen.append((self.name, self.attrs))

        def __exit__(self, *exc):
            pass

    with mock.patch.object(tracing, "TraceAnnotation", Annotation):
        clock = StepClock()
        clock.begin_step()
        clock.describe("ragged", rows=3, tokens=70, width=128)
        clock.launch(passes=4)
        clock.end_step()
        assert (clock.last_kind, clock.last_width) == ("ragged", 128)
        clock.begin_step()
        clock.describe("decode", rows=3, tokens=3)
        clock.launch()
        clock.end_step()
        assert (clock.last_kind, clock.last_width) == ("decode", 0)
        clock.idle()
        assert clock.last_width == 0
    launches = [attrs for name, attrs in seen if name == "step.launch"]
    assert launches == [
        {"kind": "ragged", "rows": 3, "tokens": 70, "width": 128,
         "passes": 4},
        {"kind": "decode", "rows": 3, "tokens": 3}]


def clocked_step(clock, fake, kind, wait, width=0):
    """A step that waits ``wait`` seconds for the dispatch before it."""
    clock.begin_step()
    clock.describe(kind, rows=2, tokens=2, width=width)
    fake.now += 0.001
    clock.launch()
    fake.now += 0.001
    clock.wait(kind)
    fake.now += wait
    clock.enter("postprocess")
    fake.now += 0.002
    return clock.end_step()


def test_a_wide_step_after_thirty_two_narrow_ones_is_not_a_slow_step():
    fake = types.SimpleNamespace(now=10.0)
    fake.monotonic = lambda: fake.now
    fake.thread_time = lambda: fake.now
    with mock.patch.object(tracing, "time", fake):
        clock = StepClock()
        # a prompt every other step: the decode step after a narrow ragged
        # step waits out its 30 ms, the ragged step a decode step's 20 ms
        for _ in range(tracing.SLOW_WINDOW + 2):
            clocked_step(clock, fake, "ragged", 0.020, width=512)
            clocked_step(clock, fake, "decode", 0.030)
        assert not clock.slow_steps
        # the rare wide step: launched by a step like the others, waited
        # out (93 ms, three times a narrow one) by the decode step after it
        clocked_step(clock, fake, "ragged", 0.020, width=2048)
        clocked_step(clock, fake, "decode", 0.093)
        # and the first narrow step after it has a reference of its own
        clocked_step(clock, fake, "ragged", 0.020, width=512)
        clocked_step(clock, fake, "decode", 0.030)
        assert not clock.slow_steps
        assert sum(clock.slow_seconds["decode"].values()) == 0.0
        # the same wait behind a NARROW step is a stall, and is booked
        clocked_step(clock, fake, "ragged", 0.020, width=512)
        clocked_step(clock, fake, "decode", 0.093)
        (slow,) = clock.slow_steps
        assert (slow["kind"], slow["after"], slow["after_width"],
                slow["cause"]) == ("decode", "ragged", 512, "wait")
        assert slow["reference"] == pytest.approx(0.034)


def test_warmup_of_a_default_configuration_builds_what_the_chip_builds():
    """The default widths (512 and the 2048-token budget, 64 slots) on the
    CPU: warm-up compiles the ragged and the decode program and nothing
    else that runs a prompt, at both widths, and a mixed batch after it
    compiles nothing. (The model has fewer positions than the budget: the
    budget-wide runs are made up of several prompts.)"""
    cfg = ModelConfig.from_pretrained("tiny-llama")
    mesh = one_device()
    eng = LLMEngine(
        EngineConfig(model=cfg, cache=CacheConfig(num_blocks=512),
                     mesh=MeshConfig(data=1, tensor=1)),
        mesh=mesh, params=init_or_load(cfg, mesh, seed=3))
    assert eng.config.scheduler.ragged_stream_widths == (512, 2048)
    eng.warmup()
    fields = eng.perf.stats_fields()
    assert {kind for kind, _ in fields["compile_counts"]} == {
        "ragged", "decode_multi"}
    assert builds_by_width(fields["compile_counts"]) == {
        512: 6, 2048: 6}, fields["compile_counts"]
    assert fields["unexpected_recompiles"] == 0
    # six prompts that arrive together fill a budget-wide step, the
    # stragglers join the others' decode rows in narrow ones
    sampled = SamplingParams(temperature=0.7, max_tokens=4, ignore_eos=True)
    requests = [(f"w{i}", prompt(i, 200), greedy(4) if i % 2 else sampled)
                for i in range(6)]
    requests += [("late", prompt(7, 30), greedy(4)),
                 ("later", prompt(8, 9), sampled)]
    _, _, steps = serve(eng, requests, arrive_at=(0, 0, 0, 0, 0, 1, 2))
    assert {w for w, _ in steps} == {512, 2048}, steps
    fields = eng.perf.stats_fields()
    assert fields["unexpected_recompiles"] == 0, fields["compile_counts"]
