"""A replica's start in parts (engine/tracing.py StartClock) and a
program's first call in stages (engine/perf_accounting.py BuildStages,
CompileTracker): the spans, what a tiny engine exports of them, the
stages against the first call's wall time, the persistent cache's answers
across two processes, and the names programs are told apart by."""

import asyncio
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine import perf_accounting as pa
from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.model_runner import (
    _DECODE_INPUTS,
    _RAGGED_INPUTS,
    StepLayout,
)
from production_stack_tpu.engine.server import EngineServer
from production_stack_tpu.engine.tracing import (
    SLOW_WINDOW,
    START_PHASES,
    START_TOP,
    StartClock,
    StepClock,
)
from production_stack_tpu.parallel.mesh import MeshConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAGE_EVENTS = {stage: event for event, stage in pa._JAX_STAGES.items()}


# -- StartClock ----------------------------------------------------------------

# each layout: (name, depth) in the order the spans open; a span closes
# when one of its depth or less opens, and all at the end
LAYOUTS = {
    "flat": [("a", 0), ("b", 0)],
    "children_in_a_row": [("engine_build", 0), ("tokenizer", 1),
                          ("weights.make", 1), ("kv_pool", 1)],
    "three_deep": [("engine_build", 0), ("weights.make", 1), ("leaf", 2),
                   ("kv_pool", 1)],
    "parent_without_children": [("engine_build", 0), ("server_bind", 0)],
}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_spans_nest_and_self_time_is_never_negative(layout):
    clock = StartClock()
    opened = []  # (depth, index)
    for name, depth in LAYOUTS[layout]:
        while opened and opened[-1][0] >= depth:
            clock.end(opened.pop()[1])
        opened.append((depth, clock.begin(name)))
        time.sleep(0.002)
    while opened:
        clock.end(opened.pop()[1])
    by_name = {s[0]: s for s in clock.spans}
    assert by_name["process"][3] is None
    for (name, depth), nxt in zip(LAYOUTS[layout], LAYOUTS[layout][1:]):
        if nxt[1] == depth + 1:
            assert by_name[nxt[0]][3] == name
    for name, start, end, parent in clock.spans:
        assert end is not None and end >= start
        inside = sum(e - s for _, s, e, p in clock.spans if p == name)
        assert (end - start) - inside >= 0.0
        if parent is not None:
            assert by_name[parent][1] <= start and end <= by_name[parent][2]
    seconds = clock.seconds()
    assert set(START_PHASES) <= set(seconds)
    assert seconds["engine_build.self"] >= 0.0
    assert seconds["process"] >= 0.0


def test_ready_fixes_the_numbers_and_an_open_span_is_not_counted():
    clock = StartClock()
    assert clock.to_ready == 0.0
    i = clock.begin("warmup")
    assert clock.open_since("warmup") >= 0.0
    assert clock.seconds()["warmup"] == 0.0
    assert clock.end(i) == pytest.approx(clock.seconds()["warmup"])
    clock.mark_ready()
    ready = clock.to_ready
    clock.mark_ready()  # the first call holds
    assert clock.to_ready == ready >= clock.seconds()["process"]
    before = clock.seconds()
    with clock.span("weights.make"):  # a wake: recorded, not added
        pass
    assert clock.seconds() == before
    assert clock.spans[-1][0] == "weights.make"
    snap = clock.snapshot()
    assert snap["outside_spans_seconds"] == pytest.approx(
        ready - sum(before[p] for p in START_TOP))


# -- a tiny engine: what it exports --------------------------------------------

@pytest.fixture(scope="module")
def served():
    """(metrics text, /debug/perf) of a tiny engine after one request."""
    from aiohttp.test_utils import TestClient, TestServer

    server = EngineServer(EngineConfig(
        model=ModelConfig.from_pretrained("tiny-llama"),
        cache=CacheConfig(block_size=4, num_blocks=512),
        scheduler=SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=64),
        mesh=MeshConfig(data=1, tensor=1)))

    async def run():
        async with TestClient(TestServer(server.build_app())) as client:
            r = await client.post("/v1/completions", json={
                "model": "tiny-llama", "prompt": "hello", "max_tokens": 4,
                "temperature": 0, "ignore_eos": True})
            assert r.status == 200
            text = await (await client.get("/metrics")).text()
            return text, await (await client.get("/debug/perf")).json()

    return asyncio.run(run())


def _samples(text: str, family: str) -> dict:
    """{label block: value} of one family's samples."""
    out = {}
    for line in text.splitlines():
        if line.startswith(family + "{") or line.startswith(family + " "):
            head, _, value = line.rpartition(" ")
            out[head[len(family):]] = float(value)
    return out


@pytest.mark.parametrize("phase", START_PHASES)
def test_engine_exports_every_phase(served, phase):
    text, perf = served
    by_label = _samples(text, "vllm:engine_start_seconds")
    (value,) = [v for k, v in by_label.items() if f'phase="{phase}"' in k]
    assert value == pytest.approx(perf["start"]["seconds"][phase])
    assert value >= 0.0
    assert perf["startup_seconds"][phase] == round(value, 2)
    if phase in ("process", "engine_build", "weights.make", "kv_pool",
                 "server_bind"):
        assert value > 0.0 and any(
            s["name"] == phase for s in perf["start"]["spans"])


def test_startup_seconds_keeps_its_three_keys_and_ready_is_stamped(served):
    text, perf = served
    assert {"backend_open", "engine_build", "warmup"} <= set(
        perf["startup_seconds"])
    start = perf["start"]
    assert start["ready_at"] is not None
    to_ready = _samples(text, "vllm:engine_start_to_ready_seconds")
    assert list(to_ready.values()) == [
        pytest.approx(start["to_ready_seconds"])]
    sec = start["seconds"]
    assert sec["engine_build"] == pytest.approx(sum(
        sec[p] for p in START_PHASES if p not in START_TOP))
    assert start["to_ready_seconds"] >= sum(sec[p] for p in START_TOP) - 1e-6
    for family, parts in (
            ("vllm:engine_start_process_seconds", ("process",)),
            ("vllm:engine_start_backend_open_seconds", ("backend_open",)),
            ("vllm:engine_start_tokenizer_seconds", ("tokenizer",)),
            ("vllm:engine_start_weights_seconds",
             ("weights.make", "weights.quantize", "weights.lay_out")),
            ("vllm:engine_start_kv_pool_seconds", ("kv_pool",))):
        assert list(_samples(text, family).values()) == [
            pytest.approx(sum(sec[p] for p in parts))]
    assert list(_samples(text, "vllm:engine_warmup_seconds").values()) == [0.0]
    # a preset has no tokenizer directory: the byte tokenizer, and said so
    assert start["tokenizer_loader"] == "bytes"


@pytest.mark.parametrize("stage", pa.BUILD_STAGES)
def test_engine_exports_every_stage_and_the_builds_add_up(served, stage):
    text, perf = served
    builds = perf["builds"]
    names = [f"{b['kind']}:{b['bucket']}" for b in builds]
    assert len(names) == len(set(names))
    assert {"ragged", "decode_multi", "other"} <= {b["kind"] for b in builds}
    by_label = _samples(text, "vllm:program_build_seconds_total")
    of_stage = {k: v for k, v in by_label.items() if f'stage="{stage}"' in k}
    (total,) = _samples(text, f"vllm:program_{stage}_seconds_total").values()
    assert total == pytest.approx(sum(of_stage.values()))
    # the tracked programs' share of it is what their builds hold
    tracked = [b for b in builds if b["kind"] != "other"]
    assert sum(v for k, v in of_stage.items() if 'kind="other"' not in k) \
        == pytest.approx(sum(b["stages"][stage] for b in tracked))
    for b in tracked:
        assert b["seconds"] == pytest.approx(
            sum(b["stages"].values()), abs=2e-3)
        assert b["engine_step"] >= 1 and b["stages"]["trace"] > 0.0
    # the families that keep their names are computed from the same builds
    assert sum(_samples(text, "vllm:compile_events_total").values()) \
        == len(tracked) == perf["compile"]["total_events"]
    (wall,) = _samples(text, "vllm:compile_time_seconds_total").values()
    assert wall == pytest.approx(sum(b["seconds"] for b in tracked))
    built = sum(_samples(text, "vllm:program_builds_total").values())
    assert built >= len(names)  # `other` of the whole process
    (hits,) = _samples(text, "vllm:compile_cache_hits_total").values()
    (misses,) = _samples(text, "vllm:compile_cache_misses_total").values()
    assert hits + misses >= len(builds)


# -- CompileTracker and the listeners ------------------------------------------

def test_a_new_signature_is_one_build_and_a_seen_one_costs_nothing(
        monkeypatch):
    builds = []
    tracker = pa.CompileTracker(
        "ragged", jax.jit(lambda x: jnp.sin(x) @ x.T),
        lambda kind, bucket, seconds, build: builds.append(build))
    x = np.ones((8, 8), np.float32)
    t0 = time.monotonic()
    tracker(x).block_until_ready()
    wall = time.monotonic() - t0
    (build,) = builds
    assert (build["kind"], build["bucket"]) == ("ragged", "8x8")
    stages = build["stages"]
    assert set(stages) == set(pa.BUILD_STAGES)
    assert stages["trace"] > 0 and stages["lower"] > 0
    assert stages["compile"] + stages["cache_load"] > 0
    assert build["cache_hits"] + build["cache_misses"] >= 1
    assert sum(stages.values()) == pytest.approx(build["seconds"], abs=2e-3)
    assert build["seconds"] <= wall
    # a seen signature: no build, and the listeners are not reached
    calls = []
    state = pa.BuildStages._state
    monkeypatch.setattr(pa.BuildStages, "_state",
                        lambda self: calls.append(1) or state(self))
    for _ in range(3):
        tracker(x).block_until_ready()
    assert len(builds) == 1 and not calls
    tracker(np.ones((4, 8), np.float32))  # a new signature: another
    assert len(builds) == 2 and calls
    assert builds[1]["bucket"] == "4x8"


def test_two_signatures_of_one_name_are_two_programs():
    builds = []
    tracker = pa.CompileTracker(
        "sample", lambda *a, **k: None,
        lambda kind, bucket, seconds, build: builds.append(bucket))
    a = np.zeros((2, 8), np.int32)
    tracker(a)
    tracker(a.astype(np.int64))  # same shape, another dtype
    tracker(a, flag=True)
    assert builds == ["2x8", "2x8#2", "2x8:flag"]


def _fire(stage: str, seconds: float, hit: bool = False, inner=()) -> None:
    """What jax's `log_elapsed_time` records around one stage, with
    ``inner`` stages opened inside it."""
    event = STAGE_EVENTS[stage]
    jax.monitoring.record_scalar(event, time.time(), fun_name="drill")
    for args in inner:
        _fire(*args)
    if hit:
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        jax.monitoring.record_event_duration_secs(
            "/jax/compilation_cache/cache_retrieval_time_sec", seconds / 2)
    jax.monitoring.record_event_duration_secs(
        event, seconds, fun_name="drill")


def test_an_event_with_no_build_open_lands_in_other():
    stages = pa.build_stages()
    n, before = stages.other_builds, dict(stages.other_seconds)
    hits, misses = stages.other_hits, stages.other_misses
    _fire("trace", 0.5, inner=[("trace", 0.2)])  # nested: counted once
    _fire("lower", 0.25)
    _fire("compile_or_load", 1.0, hit=True)
    _fire("trace", 0.125)
    _fire("compile_or_load", 2.0)
    assert stages.other_builds == n + 2
    assert (stages.other_hits, stages.other_misses) == (hits + 1, misses + 1)
    grew = {s: stages.other_seconds[s] - before[s] for s in before}
    assert grew == pytest.approx({"trace": 0.625, "lower": 0.25,
                                  "cache_load": 1.0, "compile": 2.0,
                                  "first_run": 0.0})
    first, second = list(stages.other)[-2:]
    assert first["kind"] == second["kind"] == "other"
    assert first["bucket"].startswith("drill")
    assert first["bucket"] != second["bucket"]
    assert first["cache_hit"] and not second["cache_hit"]
    assert first["cache_retrieval_seconds"] == pytest.approx(0.5)
    assert first["stages"]["trace"] == pytest.approx(0.5)


def test_events_inside_a_build_are_the_build_s_and_split_by_the_cache():
    stages = pa.build_stages()
    n = stages.other_builds
    build = pa.new_build("ragged", "w512:greedy")
    with stages.building(build):
        _fire("trace", 1.0, inner=[("trace", 0.25), ("lower", 0.125)])
        _fire("lower", 0.5)
        _fire("compile_or_load", 3.0, hit=True)
        _fire("compile_or_load", 0.25)  # a helper the cache did not hold
    assert stages.other_builds == n
    assert build["stages"] == pytest.approx(
        {"trace": 0.875, "lower": 0.625, "cache_load": 3.0, "compile": 0.25,
         "first_run": 0.0})
    assert (build["cache_hits"], build["cache_misses"]) == (1, 1)
    assert build["cache_retrieval_seconds"] == pytest.approx(1.5)


CACHE_DRILL = """
import json, sys
import numpy as np
from production_stack_tpu.compile_cache import configure_compile_cache
configure_compile_cache()
import jax, jax.numpy as jnp
from production_stack_tpu.engine import perf_accounting as pa
builds = []
tracker = pa.CompileTracker(
    "ragged", jax.jit(lambda x: jnp.tanh(x @ x.T).sum(axis=0)),
    lambda kind, bucket, seconds, build: builds.append(build))
for n in (16, 32):
    tracker(np.ones((n, 8), np.float32)).block_until_ready()
other = pa.build_stages()
print(json.dumps({
    "hits": sum(b["cache_hits"] for b in builds) + other.other_hits,
    "misses": sum(b["cache_misses"] for b in builds) + other.other_misses,
    "cache_hit": [b["cache_hit"] for b in builds],
    "compile": sum(b["stages"]["compile"] for b in builds),
    "cache_load": sum(b["stages"]["cache_load"] for b in builds)}))
"""


def test_a_second_process_over_the_same_cache_counts_hits_and_no_misses(
        tmp_path):
    import json

    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    env.pop("XLA_FLAGS", None)  # one device: the flag is part of the key
    said = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", CACHE_DRILL], env=env,
                             capture_output=True, text=True, timeout=240)
        assert out.returncode == 0, out.stderr[-2000:]
        said.append(json.loads(out.stdout.strip().splitlines()[-1]))
    cold, warm = said
    assert cold["misses"] >= 2 and cold["hits"] == 0
    assert cold["cache_hit"] == [False, False] and cold["compile"] > 0
    assert warm["hits"] == cold["misses"] and warm["misses"] == 0
    assert warm["cache_hit"] == [True, True]
    assert warm["compile"] == 0.0 and warm["cache_load"] > 0


# -- names ---------------------------------------------------------------------

def _layout(spec, width: int, slots: int = 4, ragged: bool = True):
    tokens = (1, width) if ragged else (slots, 1)
    arrays = [np.zeros(tokens, np.int32)] + [
        np.zeros((slots,), np.float32)] * (len(spec) - 1)
    return StepLayout.of(spec, arrays)


FLAGS = dict(use_penalties=False, use_controls=False, use_grammar=False)
PROGRAMS = [
    ("ragged", 512, dict(greedy_only=True), "w512:greedy"),
    ("ragged", 2048, dict(greedy_only=True), "w2048:greedy"),
    ("ragged", 512, dict(greedy_only=False), "w512:sampled"),
    ("ragged", 2048, dict(greedy_only=False, use_grammar=True),
     "w2048:sampled+grammar"),
    ("decode_multi", 64, dict(greedy_only=True, want_logprobs=False),
     "w64:greedy"),
    ("decode_multi", 64, dict(greedy_only=False, want_logprobs=False),
     "w64:sampled"),
    ("decode_multi", 64, dict(greedy_only=True, want_logprobs=True),
     "w64:greedy+logprobs"),
    ("decode_multi", 64, dict(greedy_only=False, use_penalties=True,
                              use_controls=True),
     "w64:sampled+penalties+controls"),
]


@pytest.mark.parametrize("kind,width,flags,name", PROGRAMS,
                         ids=[f"{p[0]}:{p[3]}" for p in PROGRAMS])
def test_programs_are_named_by_width_and_variant(kind, width, flags, name):
    ragged = kind == "ragged"
    spec = _RAGGED_INPUTS[:-1] if ragged else _DECODE_INPUTS
    layout = _layout(spec, width, slots=64, ragged=ragged)
    packed = np.zeros(sum(int(np.prod(f[1])) for f in layout.fields),
                      np.int32)
    kwargs = {**FLAGS, **flags, "layout": layout, "lora_bank": None}
    assert pa.program_bucket(({}, {}, packed), kwargs) == name


def test_the_names_of_one_run_are_distinct():
    names = [f"{kind}:{name}" for kind, _, _, name in PROGRAMS]
    assert len(set(names)) == len(names)
    # an adapter bank is a signature of its own
    layout = _layout(_RAGGED_INPUTS[:-1], 512)
    assert pa.program_bucket((), dict(
        FLAGS, greedy_only=True, layout=layout, lora_bank={"wq": 1})) \
        == "w512:greedy+lora"


# -- a build inside a step -----------------------------------------------------

def test_a_build_inside_a_step_reaches_the_slow_step_ring_with_its_stages():
    clock = StepClock()

    def step(build=None, seconds=0.0):
        clock.begin_step()
        clock.describe("decode", 4, 4)
        clock.launch()
        if build is not None:
            time.sleep(seconds)
            clock.note_build(build)
        clock.wait("decode")
        clock.end_step()

    for _ in range(SLOW_WINDOW + 1):
        step()
    assert not clock.slow_steps
    build = pa.new_build("decode_multi", "w4:greedy+logprobs")
    build["seconds"] = 0.05
    build["stages"].update(trace=0.02, lower=0.01, cache_load=0.02)
    step(build, 0.05)
    assert build["engine_step"] == clock.step_num
    (slow,) = clock.slow_steps
    assert slow["cause"] == "compile" and slow["step"] == clock.step_num
    (held,) = slow["builds"]
    assert held["bucket"] == "w4:greedy+logprobs"
    assert held["stages"]["cache_load"] == pytest.approx(0.02)
    assert clock.slow_seconds["decode"]["compile"] >= 0.05
    # outside a step a build belongs to none
    outside = pa.new_build("sample", "4x32")
    clock.note_build(outside)
    assert outside["engine_step"] is None
