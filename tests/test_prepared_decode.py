"""A decode step is built while the one before it runs and launched when
that one lands, or a lead before where the landing is foreseen
(engine/engine.py ``_run_decode``, ``_launch_ahead``): the same tokens as
the engine that builds each step after the landing of the one before,
for a tiny model of every family the benchmark's decode cells serve;
never a decode program launched without the arrival probe asked, at a
decode program's landing, at a ragged step's and a lead before a
landing, and never more than one queued behind the one in flight; the
batches that need the last results on the host stay in order; what a
request is charged is its own dispatch; and the counters that say how
often the order engages and what it costs, through the benchmark's
reader.

"With the lead" here is the engine given a wait that returns at once and
a landing foreseen in the past: the question whether the prepared step
may be queued is then asked as soon as it is prepared, at every step,
whatever the clock says.

"In order" here is the same engine with its arrival probe held true: a
prepared step is then dropped at every landing, and every decode dispatch
is built from the host's tokens, after the landing, as before this
order existed."""

import dataclasses
import json
import os
import types

import jax
import numpy as np
import pytest

from chipbench import layers, prom
from production_stack_tpu.engine.config import (
    MODEL_PRESETS,
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sampling import SamplingParams
from production_stack_tpu.parallel.mesh import MeshConfig, build_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _hf(name: str) -> dict:
    with open(os.path.join(ROOT, "chipbench", "tests", "configs", name,
                           "config.json")) as f:
        return json.load(f)


# a tiny model of each family a decode cell serves, with the block size
# its own tests run it at
FAMILIES = {
    "dense_gqa": (lambda: ModelConfig.from_pretrained("tiny-llama"), 4),
    "sparse_experts": (lambda: ModelConfig.from_pretrained("tiny-olmoe"), 4),
    "looped_stack": (lambda: ModelConfig.from_pretrained("tiny-ouro"), 4),
    "kda_gqa": (lambda: dataclasses.replace(ModelConfig.from_hf_config(
        _hf("tiny-solar-open2"), "tiny-solar"), dtype="float32"), 16),
    "mamba_window_cross": (lambda: MODEL_PRESETS["tiny-phi4flash"], 4),
    "kda_mla": (lambda: dataclasses.replace(ModelConfig.from_hf_config(
        _hf("tiny-kimi-linear"), "tiny-kimi-linear"), dtype="float32"), 16),
}


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(MeshConfig(), devices=jax.devices()[:1])


def with_lead(eng) -> LLMEngine:
    eng.landing_wait = lambda stamp: None
    eng._landing_expected = lambda pending: 0.0
    return eng


def make_engine(mesh, family="dense_gqa", params=None, num_blocks=256,
                slots=4, in_order=False, prefix_caching=True, lead=False,
                **sched) -> LLMEngine:
    model, block = FAMILIES[family]
    cfg = EngineConfig(
        model=model(), cache=CacheConfig(
            block_size=block, num_blocks=num_blocks,
            enable_prefix_caching=prefix_caching),
        scheduler=SchedulerConfig(max_num_seqs=slots,
                                  max_num_batched_tokens=32, **sched),
        mesh=MeshConfig(data=1, tensor=1))
    eng = LLMEngine(cfg, mesh=mesh, params=params, num_blocks=num_blocks)
    if in_order:
        eng.arrival_probe = lambda: True
    return with_lead(eng) if lead else eng


def sp(max_tokens, **kw):
    kw.setdefault("temperature", 0.0)
    kw.setdefault("ignore_eos", True)
    return SamplingParams(max_tokens=max_tokens, **kw)


def prompt(n, seed, vocab=256):
    return [int(t) for t in np.random.default_rng(seed).integers(1, vocab, n)]


def drive(eng, script=(), limit=600) -> dict:
    """Step by hand to the end. ``script``: (when(tokens so far), act(eng))
    pairs, each run once, before the first step at which ``when`` holds:
    arrivals and aborts hang on what has been generated and not on the
    number of step() calls, which differs between the two orders."""
    toks: dict = {}
    todo = list(script)
    for _ in range(limit):
        for item in [i for i in todo if i[0](toks)]:
            todo.remove(item)
            item[1](eng)
        if not eng.has_unfinished() and not todo:
            return toks
        for o in eng.step():
            toks.setdefault(o.request_id, []).extend(o.new_token_ids)
    raise AssertionError("engine did not drain")


# -- (a) the same tokens as the in-order engine, family by family -------------

def _mixed_run(eng, stop_id):
    """Four requests over four slots and a pool that cannot hold them all:
    one to its ``max_tokens``, one that stops on a token while its next
    row is in flight, one aborted in flight, one that arrives while the
    others decode."""
    def add(rid, ids, sampling):
        return lambda e: e.add_request(rid, prompt_token_ids=ids,
                                       sampling=sampling)
    script = [
        (lambda t: True, add("bound", prompt(9, 1), sp(7))),
        (lambda t: True, add("stops", prompt(11, 2), sp(
            24, stop_token_ids=[stop_id]))),
        (lambda t: True, add("aborted", prompt(6, 3), sp(40))),
        (lambda t: len(t.get("bound", ())) >= 3,
         add("arrives", prompt(13, 4), sp(12, temperature=0.8, top_k=20,
                                          seed=11))),
        (lambda t: len(t.get("aborted", ())) >= 9,
         lambda e: e.abort_request("aborted")),
    ]
    return drive(eng, script)


@pytest.mark.parametrize("lead", [False, True], ids=["at_landing", "lead"])
@pytest.mark.parametrize("family,multi_step", [
    *((f, 1) for f in FAMILIES), ("dense_gqa", 2), ("mamba_window_cross", 2)])
def test_prepared_equals_in_order(mesh, family, multi_step, lead):
    """One engine, the run twice: in order (the probe held true), then as
    it serves, launching at the landing or with the lead. Without a
    prefix cache, so that the second run computes what the first did."""
    eng = make_engine(mesh, family, num_blocks=32 // FAMILIES[family][1],
                      in_order=True, multi_step=multi_step,
                      prefix_caching=False)
    # the token that stops "stops": one it generates a few steps in
    alone = drive(eng, [(lambda t: True, lambda e: e.add_request(
        "stops", prompt_token_ids=prompt(11, 2), sampling=sp(10)))])["stops"]
    stop_id = next(t for i, t in enumerate(alone) if i >= 4
                   and t not in alone[:i])
    preempted = []
    real = eng.scheduler._preempt
    eng.scheduler._preempt = lambda seq: (preempted.append(seq.request_id),
                                          real(seq))[1]
    want = _mixed_run(eng, stop_id)
    assert eng.decode_prepared_launches == 0 and preempted
    dispatches, eng.arrival_probe = eng.decode_dispatches, None
    del preempted[:]
    if lead:
        with_lead(eng)
    got = _mixed_run(eng, stop_id)
    assert eng.decode_prepared_launches > (
        eng.decode_dispatches - dispatches) // 2
    # the stop token and the tight pool hold some launches to the landing
    assert (0 < eng.decode_ahead_launches < eng.decode_prepared_launches
            ) if lead else eng.decode_ahead_launches == 0
    for rid in ("bound", "stops", "arrives"):
        assert got[rid] == want[rid], rid
    assert len(got["bound"]) == 7 and len(got["arrives"]) == 12
    assert got["stops"] == alone[:alone.index(stop_id) + 1]
    # the abort falls after another dispatch in one order than in the
    # other: what each saw of the sequence is one run of tokens
    a, b = sorted((got["aborted"], want["aborted"]), key=len)
    assert a and b[:len(a)] == a
    # the pool was tight enough to preempt, in both orders
    assert preempted
    assert not eng.has_unfinished() and eng._pending_decode is None


# -- (d) a prefix-cache hit after a stop with a row in flight ------------------

@pytest.mark.parametrize("in_order,lead", [
    (False, False), (True, False), (False, True)],
    ids=["prepared", "in_order", "lead"])
def test_a_prefix_hit_after_a_stop_reads_what_was_committed(mesh, in_order,
                                                            lead):
    """The stopped sequence's surplus row was in flight when its blocks
    were committed and released; a prompt that continues it hits those
    blocks and must read the rows the in-order engine wrote there."""
    base = make_engine(mesh, in_order=True)
    first = prompt(10, 5)
    ref = drive(base, [(lambda t: True, lambda e: e.add_request(
        "a", prompt_token_ids=first, sampling=sp(20)))])["a"]
    stop_id = next(t for i, t in enumerate(ref) if i >= 9
                   and t not in ref[:i])
    cut = ref[:ref.index(stop_id) + 1]
    follow_up = first + cut + prompt(3, 6)

    def two_rounds(eng):
        one = drive(eng, [(lambda t: True, lambda e: e.add_request(
            "a", prompt_token_ids=first,
            sampling=sp(20, stop_token_ids=[stop_id])))])["a"]
        seq = eng.add_request("b", prompt_token_ids=follow_up,
                              sampling=sp(8))
        two = drive(eng)["b"]
        return one, two, seq.num_cached_tokens

    want = two_rounds(make_engine(mesh, params=base.runner.params,
                                  in_order=True))
    eng = make_engine(mesh, params=base.runner.params, in_order=in_order,
                      lead=lead)
    got = two_rounds(eng)
    assert (eng.decode_ahead_launches > 0) is lead
    assert got[:2] == want[:2] and got[0] == cut
    # a real hit; the prepared order's surplus row has written the stop
    # token's own row by the time anything reads it, so its blocks may
    # be committed one token further
    assert got[2] >= want[2] >= (len(first) + len(cut)) // 4 * 4 - 4
    assert (eng.decode_prepared_launches > 0) is not in_order


# -- (b) nothing is launched ahead of an arrival -------------------------------

def _dispatch_log(eng):
    """Every program launched from here on, in order."""
    log = []
    for attr, kind in (("_ragged", "ragged"), ("_decode_multi", "decode")):
        real = getattr(eng.runner, attr)

        def program(*a, _real=real, _kind=kind, **kw):
            log.append(_kind)
            return _real(*a, **kw)

        setattr(eng.runner, attr, program)
    return log


def test_an_arrival_at_the_landing_goes_before_the_prepared_step(mesh):
    eng = make_engine(mesh)
    eng.add_request("r0", prompt_token_ids=prompt(8, 1), sampling=sp(30))
    for _ in range(4):
        eng.step()
    assert eng._pending_decode is not None
    log = _dispatch_log(eng)
    arrived = []

    def probe():  # the request reaches the intake while the thread waits
        arrived.append(len(log))
        return True

    eng.arrival_probe = probe
    prepared, dispatches = eng.decode_prepared_launches, eng.decode_dispatches
    out = eng.step()
    # the step in flight landed and was finished; the prepared one is gone
    assert [o.request_id for o in out] == ["r0"] and arrived == [0]
    assert log == [] and eng._pending_decode is None
    assert (eng.decode_prepared_launches, eng.decode_dispatches) == (
        prepared, dispatches)
    eng.arrival_probe = None
    eng.add_request("new", prompt_token_ids=prompt(5, 2), sampling=sp(4))
    eng.step()
    assert log == ["ragged"]  # no decode program between the two
    toks = drive(eng)
    assert len(toks["new"]) == 4 and eng.decode_prepared_launches > prepared


def test_an_arrival_at_a_ragged_landing_goes_before_the_decode_step(mesh):
    """The mirror at a ragged step's landing: what reached the intake
    while the ragged program ran keeps the decode program after it from
    being launched, and the next program is its own ragged step."""
    eng = make_engine(mesh)
    eng.add_request("r0", prompt_token_ids=prompt(8, 1), sampling=sp(30))
    eng.step()
    assert eng._pending_ragged is not None and eng._pending_decode is None
    log = _dispatch_log(eng)
    arrived = []

    def probe():  # the request reaches the intake while the thread waits
        arrived.append(eng._pending_ragged)
        return True

    eng.arrival_probe = probe
    out = eng.step()
    # the ragged step landed and was finished (asked after the landing);
    # the decode step scheduled for after it was not launched
    assert [o.request_id for o in out] == ["r0"] and arrived == [None]
    assert log == [] and eng._pending_decode is None
    assert (eng.ragged_landing_arrivals, eng.decode_dispatches) == (1, 0)
    eng.arrival_probe = None
    eng.add_request("new", prompt_token_ids=prompt(5, 2), sampling=sp(4))
    eng.step()
    assert log == ["ragged"]  # no decode program between the two
    toks = drive(eng)
    assert len(toks["new"]) == 4 and len(toks["r0"]) == 29
    # a probe that stays true is asked once a ragged landing: the step
    # after launches the decode program, as the in-order engine does
    eng.arrival_probe = lambda: True
    eng.add_request("r1", prompt_token_ids=prompt(7, 3), sampling=sp(3))
    assert len(drive(eng)["r1"]) == 3 and eng.ragged_landing_arrivals == 2


def _two_decoding(mesh):
    """Two requests decoding and a decode dispatch in flight with nothing
    queued behind it; the lead from here on."""
    eng = make_engine(mesh)
    for i in range(2):
        eng.add_request(f"r{i}", prompt_token_ids=prompt(7 + i, i),
                        sampling=sp(30))
    for _ in range(5):
        eng.step()
    assert eng._pending_decode is not None and eng.decode_ahead_launches == 0
    return with_lead(eng)


def test_a_probe_true_at_the_ahead_check_leaves_nothing_queued(mesh):
    eng = _two_decoding(mesh)
    log = _dispatch_log(eng)
    asked = []

    def probe():
        asked.append(len(log))
        return True

    eng.arrival_probe = probe
    dispatches = eng.decode_dispatches
    out = eng.step()
    # asked a lead before the landing and again at it, nothing launched
    # at either; the step in flight landed and was finished
    assert asked == [0, 0] and log == [] and eng._pending_decode is None
    assert sorted(o.request_id for o in out) == ["r0", "r1"]
    assert (eng.decode_ahead_launches, eng.decode_dispatches) == (
        0, dispatches)
    eng.arrival_probe = None
    eng.add_request("new", prompt_token_ids=prompt(5, 2), sampling=sp(4))
    eng.step()
    assert log == ["ragged"]  # the ragged step goes first
    toks = drive(eng)
    assert len(toks["new"]) == 4 and eng.decode_ahead_launches > 0
    assert eng.arrivals_behind_queued_decode == 0


def test_an_arrival_after_the_ahead_launch_is_counted_and_loses_no_token(
        mesh):
    want = drive(_two_decoding(mesh))
    eng = _two_decoding(mesh)
    log = _dispatch_log(eng)
    checks = []

    def probe():  # empty when asked; the request comes in right after
        checks.append(eng.clock.now())
        return False

    eng.arrival_probe = probe
    got: dict = {}
    for o in eng.step():
        got.setdefault(o.request_id, []).extend(o.new_token_ids)
    # queued at the check, with the dispatch before still in flight; the
    # landing was seen after it and found the next one queued
    assert log == ["decode"] and len(checks) == 1
    assert eng.decode_ahead_launches == 1
    assert eng._queued_landing_t > checks[0]
    eng.arrival_probe = None
    eng.add_request("new", prompt_token_ids=prompt(5, 2), sampling=sp(4),
                    enqueued=(checks[0], eng.clock.step_num))
    assert (eng.intake_requests, eng.arrivals_behind_queued_decode) == (1, 1)
    # one that reaches the intake after that landing waits for the
    # program in flight, as it always did: not counted
    eng.add_request("later", prompt_token_ids=prompt(5, 3), sampling=sp(4),
                    enqueued=(eng.clock.now(), eng.clock.step_num))
    assert (eng.intake_requests, eng.arrivals_behind_queued_decode) == (2, 1)
    for o in eng.step():  # finishes the program that was queued
        got.setdefault(o.request_id, []).extend(o.new_token_ids)
    assert log == ["decode", "ragged"]
    for rid, toks in drive(eng).items():
        got.setdefault(rid, []).extend(toks)
    assert len(got.pop("new")) == len(got.pop("later")) == 4
    # the queued program's tokens reached their sequences, every one
    assert got == want and all(len(t) == 26 for t in got.values())
    # the lead as it came out, for /debug/perf: launch to landing seen
    assert 0 < eng.decode_ahead_lead_max_seconds <= (
        eng.decode_ahead_lead_seconds)


@pytest.mark.parametrize("stoppable", [True, False],
                         ids=["a_token_can_stop_a_row", "no_token_can"])
def test_a_waiting_request_holds_the_launch_where_a_stop_could_free_a_slot(
        mesh, stoppable):
    """Two slots, three requests, the lead on. While one waits for a slot
    and a landed token could stop a row (a stop token, or end-of-sequence
    not ignored), the landing is waited for and `_arrival_first` reads
    the tokens; where none can, the prepared step goes ahead."""
    eng = make_engine(mesh, slots=2, lead=True)
    kw = {"ignore_eos": False} if stoppable else {}
    assert eng.tokenizer.eos_id is not None
    eng.add_request("a", prompt_token_ids=prompt(7, 1), sampling=sp(12, **kw))
    eng.add_request("b", prompt_token_ids=prompt(9, 2), sampling=sp(12))
    eng.add_request("waits", prompt_token_ids=prompt(5, 3), sampling=sp(5))
    prepared = 0
    while eng.scheduler.num_waiting:
        assert (eng.decode_ahead_launches == 0) is (
            stoppable or eng.decode_prepared_launches == 0)
        prepared = eng.decode_prepared_launches
        eng.step()
    assert prepared >= 6
    toks = drive(eng)  # nobody waits any more: the lead engages
    assert len(toks["waits"]) == 5 and eng.decode_ahead_launches > 0


@pytest.mark.parametrize("probe", ["never", "always"])
def test_at_most_one_program_is_queued_behind_the_one_in_flight(mesh, probe):
    """A decode program is launched by a thread that has fetched the one
    before, or a lead before that fetch with the probe asked and false:
    never more than one stands queued behind the one in flight, and none
    where the probe said true."""
    eng = make_engine(mesh, lead=True)
    in_flight, queued = [], []
    real_launch, real_fetch = eng.runner._decode_multi, eng._fetch

    def launch(*a, **kw):
        queued.append(len(in_flight))
        in_flight.append(1)
        return real_launch(*a, **kw)

    def fetch(result, kind):
        if kind == "decode":
            in_flight.pop()
        return real_fetch(result, kind)

    eng.runner._decode_multi, eng._fetch = launch, fetch
    if probe == "always":
        eng.arrival_probe = lambda: True
    for i in range(3):
        eng.add_request(f"r{i}", prompt_token_ids=prompt(6 + i, i),
                        sampling=sp(10 + 3 * i))
    toks = drive(eng)
    assert [len(toks[f"r{i}"]) for i in range(3)] == [10, 13, 16]
    assert not in_flight and len(queued) == eng.decode_dispatches > 10
    if probe == "always":
        assert set(queued) == {0} and eng.decode_ahead_launches == 0
    else:
        assert set(queued) == {0, 1} and eng.decode_prepared_launches > 10
        assert queued.count(1) == eng.decode_ahead_launches


@pytest.mark.parametrize("how", ["bound", "stop_token"])
def test_a_freed_slot_is_refilled_by_the_next_dispatch(mesh, how):
    """Two slots, three requests: when the landed step finishes one, by
    its bound (which the scheduler knew) or by a stop token (which only
    the landed tokens say), the waiting request's ragged step is the next
    program, as in the in-order engine."""
    eng = make_engine(mesh, slots=2)
    short, n_short = sp(6), 6
    if how == "stop_token":  # a token it generates a few steps in
        ref = drive(eng, [(lambda t: True, lambda e: e.add_request(
            "short", prompt_token_ids=prompt(7, 1),
            sampling=sp(12)))])["short"]
        stop_id = next(t for i, t in enumerate(ref) if i >= 5
                       and t not in ref[:i])
        short, n_short = sp(30, stop_token_ids=[stop_id]), ref.index(
            stop_id) + 1
    eng.add_request("short", prompt_token_ids=prompt(7, 1), sampling=short)
    eng.add_request("long", prompt_token_ids=prompt(9, 2), sampling=sp(40))
    eng.add_request("waits", prompt_token_ids=prompt(5, 3), sampling=sp(5))
    log = _dispatch_log(eng)
    got = {"short": 0}
    while got["short"] < n_short:
        # a queue that nothing frees stops no prepared launch
        before = len(log)
        for o in eng.step():
            got[o.request_id] = got.get(o.request_id, 0) + len(
                o.new_token_ids)
        assert eng.scheduler.num_waiting == 1
    assert eng.decode_prepared_launches >= n_short - 3
    # the landing that finished "short" launched nothing after it
    assert len(log) == before and eng._pending_decode is None
    eng.step()
    assert log[before:] == ["ragged"] and eng.scheduler.num_waiting == 0
    toks = drive(eng)
    assert len(toks["waits"]) == 5


# -- (c) batches that need the last results on the host stay in order ----------

@pytest.mark.parametrize("feature,sched", [
    ({"logprobs": 2}, {}),
    ({"guided_regex": "[a-z ]*"}, {}),
    ({}, {"spec_ngram_k": 3}),
], ids=["logprobs", "grammar", "spec_drafts"])
def test_what_needs_the_host_takes_the_in_order_path(mesh, feature, sched):
    eng = make_engine(mesh, **sched)
    motif = prompt(6, 9)
    eng.add_request("r", prompt_token_ids=motif * 3,
                    sampling=sp(10, **feature))
    toks = drive(eng)
    assert len(toks["r"]) == 10
    assert eng.decode_prepared_launches == 0
    if not sched:
        assert eng.decode_dispatches >= 9
    # and a plain request beside it afterwards is prepared again
    eng.add_request("plain", prompt_token_ids=motif, sampling=sp(
        10, temperature=0.7, seed=3))
    drive(eng)
    assert (eng.decode_prepared_launches > 0) is not sched


def test_a_plain_row_beside_a_logprobs_row_waits_for_it(mesh):
    eng = make_engine(mesh)
    eng.add_request("lp", prompt_token_ids=prompt(5, 1),
                    sampling=sp(4, logprobs=1))
    eng.add_request("plain", prompt_token_ids=prompt(5, 2), sampling=sp(12))
    drive(eng)
    # in order while the log-probabilities row lives, prepared after it
    assert 0 < eng.decode_prepared_launches <= 9
    assert eng.decode_dispatches == 11


# -- (e) a dispatch is charged its own launch and its own wait -----------------

def test_chip_seconds_are_the_launch_plus_the_own_wait(mesh):
    """Dispatch i is waited for in the step that launches i + 1: with
    each wait made to read 10 x i seconds, what dispatch i is charged is
    its launch and 10 x i, not its neighbour's."""
    eng = make_engine(mesh)
    assert eng.perf is not None and eng.perf.tenant_metering
    real_fetch, real_record = eng._fetch, eng.perf.record_decode
    waits, charged = [], []

    def fetch(result, kind):
        fetched, seconds = real_fetch(result, kind)
        if kind == "decode":
            waits.append(10.0 * (len(waits) + 1))
            seconds = waits[-1]
        return fetched, seconds

    def record(*a, seconds, **kw):
        charged.append(seconds)
        return real_record(*a, seconds=seconds, **kw)

    eng._fetch, eng.perf.record_decode = fetch, record
    seq = eng.add_request("r", prompt_token_ids=prompt(6, 1), sampling=sp(8))
    drive(eng)
    assert len(charged) == len(waits) == 7 and eng.decode_dispatches == 7
    for i, (c, w) in enumerate(zip(charged, waits)):
        assert w == 10.0 * (i + 1) and 0 < c - w < 5.0
    ragged = seq.chip_seconds - sum(charged)
    assert 0 <= ragged < 10.0  # the one sequence is charged all of each


# -- (f) the counters, and the benchmark's metrics over them -------------------

def test_the_counters_are_exported_and_say_what_ran(mesh):
    from production_stack_tpu.engine.metrics import EngineStatsCollector

    eng = make_engine(mesh, lead=True)
    eng.arrival_probe = lambda: False
    eng.add_request("r", prompt_token_ids=prompt(6, 1), sampling=sp(9),
                    enqueued=(eng.clock.now(), 0))
    eng.step()
    eng.arrival_probe = lambda: True  # at the ragged step's landing
    eng.step()
    eng.arrival_probe = lambda: False
    drive(eng)
    # a second comes in with its stamp before a landing that had the next
    # program queued, a third with its stamp after every landing
    eng.add_request("s", prompt_token_ids=prompt(6, 2), sampling=sp(2),
                    enqueued=(eng._queued_landing_t - 1e-4, 0))
    eng.add_request("t", prompt_token_ids=prompt(6, 3), sampling=sp(2),
                    enqueued=(eng.clock.now(), 0))
    stats = eng.stats()
    want = {"decode_dispatches": 8, "decode_prepared_launches": 7,
            "decode_ahead_launches": 7, "engine_intake_requests": 3,
            "arrivals_behind_queued_decode": 1, "ragged_landing_arrivals": 1}
    assert {k: stats[k.removeprefix("engine_") + "_total"]
            for k in want} == want
    fams = {f.name: f for f in EngineStatsCollector(eng, "m").collect()}
    assert {k: fams["vllm:" + k].samples[0].value for k in want} == want
    # and the benchmark's readers take them from the text a scrape gets
    from prometheus_client import CollectorRegistry, generate_latest

    registry = CollectorRegistry()
    registry.register(EngineStatsCollector(eng, "m"))
    ctx = _ctx(SCRAPE % dict.fromkeys(COUNTERS, 0.0),
               generate_latest(registry).decode())
    assert [layers.read(name, ctx) for name in METRICS] == [
        100.0 * 7 / 8, 100.0 * 7 / 8, 100.0 / 3]


# metric -> (the counter over the counter, better, moves, the cells that
# list it beside the eight that run decode-only steps)
DECODE_CELLS = {
    "qwen3-8b-l16.decode-heavy", "olmoe-1b-7b-l8.decode-heavy",
    "ouro-2.6b.decode-pool-bound", "solar-open2-250b-ep16-l8.decode-heavy",
    "phi-4-mini-flash-reasoning.long-decode",
    "kimi-linear-48b-a3b-ep16.long-decode", "falcon-h1-34b-l6.decode-heavy",
    "olmo-hybrid-7b-l16.decode-heavy",
    "trinity-large-preview-ep16-l8.long-context"}
METRICS = {
    "decode_prepared_launch_pct": (
        "prepared", "dispatches", "higher", "tpot_p50_ms", DECODE_CELLS),
    "decode_ahead_launch_pct": (
        "ahead", "dispatches", "higher", "tpot_p50_ms", DECODE_CELLS),
    # the decode cells that report a time to first token
    "arrival_behind_queued_decode_pct": (
        "behind", "intake", "lower", "ttft_p50_ms", {
            "qwen3-8b-l16.decode-heavy", "olmoe-1b-7b-l8.decode-heavy",
            "ouro-2.6b.decode-pool-bound",
            "solar-open2-250b-ep16-l8.decode-heavy"}),
}
COUNTERS = ("dispatches", "prepared", "ahead", "intake", "behind")
SCRAPE = """\
# HELP vllm:decode_dispatches_total decode_multi dispatches issued (decode-only steps)
# TYPE vllm:decode_dispatches_total counter
vllm:decode_dispatches_total{model_name="m"} %(dispatches)s
# HELP vllm:decode_prepared_launches_total Of them, launched at the landing of the dispatch before from inputs built and committed while it ran, its tokens left on the device
# TYPE vllm:decode_prepared_launches_total counter
vllm:decode_prepared_launches_total{model_name="m"} %(prepared)s
# HELP vllm:decode_ahead_launches_total Of those, queued behind the dispatch before a lead ahead of its landing, the intake asked and empty
# TYPE vllm:decode_ahead_launches_total counter
vllm:decode_ahead_launches_total{model_name="m"} %(ahead)s
# HELP vllm:engine_intake_requests_total Requests the engine thread took in from its intake queue
# TYPE vllm:engine_intake_requests_total counter
vllm:engine_intake_requests_total{model_name="m"} %(intake)s
# HELP vllm:arrivals_behind_queued_decode_total Of them, those that reached the intake before a landing at which the next decode program already stood queued
# TYPE vllm:arrivals_behind_queued_decode_total counter
vllm:arrivals_behind_queued_decode_total{model_name="m"} %(behind)s
"""


def _ctx(open_text, close_text):
    return types.SimpleNamespace(
        prom_open=prom.parse(open_text), prom_close=prom.parse(close_text),
        manifest={})


@pytest.mark.parametrize("name", METRICS)
@pytest.mark.parametrize("open_, close, want", [
    ((10, 8), (110, 88), 80.0),
    ((0, 0), (90, 0), 0.0),     # none of them: 0, not None
    ((5, 5), (55, 55), 100.0),
])
def test_the_metric_reads_the_share_from_recorded_metrics(name, open_, close,
                                                          want):
    num, den = METRICS[name][:2]
    texts = [SCRAPE % {**dict.fromkeys(COUNTERS, 3.0), den: float(d),
                       num: float(n)} for d, n in (open_, close)]
    value = layers.read(name, _ctx(*texts))
    assert value == want and value is not None


@pytest.mark.parametrize("name", METRICS)
def test_the_metric_reads_nothing_from_a_program_without_the_counter(name):
    old = 'vllm:decode_dispatches_total{model_name="m"} 5.0\n'
    assert layers.read(name, _ctx(old, old)) is None


@pytest.mark.parametrize("name", METRICS)
def test_the_metrics_file_matches_its_benchmark_entry(name):
    _, _, better, moves, cells = METRICS[name]
    spec = layers.load_spec(name)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    (entry,) = [m for m in bm["per_layer"] if m["name"] == name]
    assert (spec["layer"], spec["unit"], spec["source"], spec["reader"]) == (
        entry["layer"], entry["unit"], entry["source"], "prom_ratio")
    assert (entry["layer"], entry["unit"]) == ("engine step loop", "%")
    assert (entry["moves"], entry["better"]) == (moves, better)
    assert set(entry["workloads"]) == cells
    # every cell that runs decode-only steps, and no other
    assert DECODE_CELLS == {w["name"] for w in bm["workloads"]} - {
        "qwen3-8b-l16.prefill-heavy", "solar-open2-250b-ep16-l8.prefill-heavy",
        "openpangu-ultra-moe-718b-ep16-l5.long-prompt"}
    reported = {w for m in bm["end_to_end"] if m["name"] == moves
                for w in m["workloads"]}
    assert cells <= reported
    # data alone: the reader is the one the benchmark has
    assert not os.path.exists(os.path.join(layers.DIR, name + ".py"))
