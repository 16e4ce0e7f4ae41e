"""A decode step is built while the one before it runs and launched when
that one lands (engine/engine.py ``_run_decode``): the same tokens as the
engine that builds each step after the landing of the one before, for a
tiny model of every family the benchmark's decode cells serve; never a
decode program launched ahead of an arrival's ragged step; the batches
that need the last results on the host stay in order; what a request is
charged is its own dispatch; and the counter that says how often the
order engages, through the benchmark's reader.

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


def make_engine(mesh, family="dense_gqa", params=None, num_blocks=256,
                slots=4, in_order=False, prefix_caching=True,
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
    return eng


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


@pytest.mark.parametrize("family,multi_step", [
    *((f, 1) for f in FAMILIES), ("dense_gqa", 2), ("mamba_window_cross", 2)])
def test_prepared_equals_in_order(mesh, family, multi_step):
    """One engine, the run twice: in order (the probe held true), then as
    it serves. Without a prefix cache, so that the second run computes
    what the first did."""
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
    got = _mixed_run(eng, stop_id)
    assert eng.decode_prepared_launches > (
        eng.decode_dispatches - dispatches) // 2
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

@pytest.mark.parametrize("in_order", [False, True],
                         ids=["prepared", "in_order"])
def test_a_prefix_hit_after_a_stop_reads_what_was_committed(mesh, in_order):
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
    eng = make_engine(mesh, params=base.runner.params, in_order=in_order)
    got = two_rounds(eng)
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


def test_an_in_flight_program_is_never_followed_by_a_queued_one(mesh):
    """A decode program is launched only from a thread that has fetched
    the one before: at every launch nothing else is in flight."""
    eng = make_engine(mesh)
    in_flight = []
    real_launch, real_fetch = eng.runner._decode_multi, eng._fetch

    def launch(*a, **kw):
        assert not in_flight
        in_flight.append(1)
        return real_launch(*a, **kw)

    def fetch(result, kind):
        if kind == "decode":
            in_flight.pop()
        return real_fetch(result, kind)

    eng.runner._decode_multi, eng._fetch = launch, fetch
    for i in range(3):
        eng.add_request(f"r{i}", prompt_token_ids=prompt(6 + i, i),
                        sampling=sp(10 + 3 * i))
    drive(eng)
    assert eng.decode_prepared_launches > 10 and not in_flight


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


# -- (f) the counter, and the benchmark's metric over it -----------------------

def test_the_counters_are_exported_and_say_what_ran(mesh):
    from production_stack_tpu.engine.metrics import EngineStatsCollector

    eng = make_engine(mesh)
    eng.add_request("r", prompt_token_ids=prompt(6, 1), sampling=sp(9))
    drive(eng)
    stats = eng.stats()
    assert (stats["decode_dispatches_total"],
            stats["decode_prepared_launches_total"]) == (8, 7)
    fams = {f.name: f for f in EngineStatsCollector(eng, "m").collect()}
    assert fams["vllm:decode_prepared_launches"].samples[0].value == 7
    assert fams["vllm:decode_dispatches"].samples[0].value == 8
    # and the benchmark's reader takes them from the text a scrape gets
    from prometheus_client import CollectorRegistry, generate_latest

    registry = CollectorRegistry()
    registry.register(EngineStatsCollector(eng, "m"))
    close = generate_latest(registry).decode()
    zero = SCRAPE % {"dispatches": 0.0, "prepared": 0.0}
    assert layers.read(NAME, _ctx(zero, close)) == 100.0 * 7 / 8


NAME = "decode_prepared_launch_pct"
SCRAPE = """\
# HELP vllm:decode_dispatches_total decode_multi dispatches issued (decode-only steps)
# TYPE vllm:decode_dispatches_total counter
vllm:decode_dispatches_total{model_name="m"} %(dispatches)s
# HELP vllm:decode_prepared_launches_total Of them, launched at the landing of the dispatch before from inputs built and committed while it ran, its tokens left on the device
# TYPE vllm:decode_prepared_launches_total counter
vllm:decode_prepared_launches_total{model_name="m"} %(prepared)s
"""


def _ctx(open_text, close_text):
    return types.SimpleNamespace(
        prom_open=prom.parse(open_text), prom_close=prom.parse(close_text),
        manifest={})


@pytest.mark.parametrize("open_, close, want", [
    ((10, 8), (110, 88), 80.0),
    ((0, 0), (90, 0), 0.0),     # every step after a ragged one: 0, not None
    ((5, 5), (55, 55), 100.0),
])
def test_the_metric_reads_the_share_from_recorded_metrics(open_, close, want):
    texts = [SCRAPE % {"dispatches": float(d), "prepared": float(p)}
             for d, p in (open_, close)]
    value = layers.read(NAME, _ctx(*texts))
    assert value == want and value is not None


def test_the_metric_reads_nothing_from_a_program_without_the_counter():
    old = 'vllm:decode_dispatches_total{model_name="m"} 5.0\n'
    assert layers.read(NAME, _ctx(old, old)) is None


def test_the_metrics_file_matches_its_benchmark_entry():
    spec = layers.load_spec(NAME)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    (entry,) = [m for m in bm["per_layer"] if m["name"] == NAME]
    assert (spec["layer"], spec["unit"], spec["source"], spec["reader"]) == (
        entry["layer"], entry["unit"], entry["source"], "prom_ratio")
    assert entry["layer"] == "engine step loop"
    assert entry["moves"] == "tpot_p50_ms" and entry["better"] == "higher"
    decode_cells = {w["name"] for w in bm["workloads"]} - {
        "qwen3-8b-l16.prefill-heavy", "solar-open2-250b-ep16-l8.prefill-heavy",
        "openpangu-ultra-moe-718b-ep16-l5.long-prompt"}
    assert set(entry["workloads"]) == decode_cells
    assert not os.path.exists(os.path.join(layers.DIR, NAME + ".py"))
