"""The per-layer metrics of the layer `engine start`: their files against
BENCHMARK.json, their readers on hand-made scrapes of the window's open,
and nothing (None) for a program that exports none of their families, as
the commit before them does."""

import json
import os
import types

import pytest

from chipbench import layers

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
START = "vllm:engine_start_"
# what a start exported at the window's open (a family summed over labels)
OPEN = {
    START + "process_seconds": 3.5,
    START + "backend_open_seconds": 6.25,
    START + "weights_seconds": 9.0,
    START + "kv_pool_seconds": 0.75,
    START + "to_ready_seconds": 34.5,
    START + "seconds": 99.0,  # the labelled family, summed: read by none
    "vllm:program_trace_seconds_total": 12.0,
    "vllm:program_lower_seconds_total": 4.5,
    "vllm:program_compile_seconds_total": 0.0,
    "vllm:program_cache_load_seconds_total": 7.25,
    "vllm:program_first_run_seconds_total": 2.5,
    "vllm:program_builds_total": 41.0,
    "vllm:compile_cache_hits_total": 36.0,
    "vllm:compile_cache_misses_total": 4.0,
}
EXPECTED = {
    "start_process_s": 3.5, "start_backend_open_s": 6.25,
    "start_weights_s": 9.0, "start_kv_pool_s": 0.75,
    "start_to_ready_s": 34.5, "program_trace_lower_s": 16.5,
    "program_compile_s": 0.0, "program_cache_load_s": 7.25,
    "program_first_run_s": 2.5, "programs_built": 41.0,
    "compile_cache_miss_pct": 10.0,
}


def ctx(prom_open, prom_close=None):
    return types.SimpleNamespace(
        records=[], seconds=10.0, prom_open=prom_open,
        prom_close=prom_close if prom_close is not None else prom_open,
        polls=[], flight=[], trace=None, hf={}, manifest={}, mix={},
        chips=1, peaks=None)


@pytest.fixture(scope="module")
def bm():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_layer_has_these_eleven_and_all_move_setup_s(bm):
    mine = [m for m in bm["per_layer"] if m["layer"] == "engine start"]
    assert {m["name"] for m in mine} == set(EXPECTED)
    cells = [w["name"] for w in bm["workloads"]]
    for m in mine:
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert set(m["workloads"]) <= set(cells) and len(m["workloads"]) >= 10
    # appended: nothing that was there moved
    assert [m["name"] for m in bm["per_layer"][-11:]] == [
        m["name"] for m in mine]


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_canned_scrape(name, bm):
    spec = layers.load_spec(name)
    (entry,) = [m for m in bm["per_layer"] if m["name"] == name]
    assert (spec["layer"], spec["unit"], spec["source"]) == (
        entry["layer"], entry["unit"], entry["source"])
    assert os.path.exists(os.path.join(layers.DIR, name + ".py"))
    # the close of the window is not read: a delta would be 0
    assert layers.read(name, ctx(OPEN, {})) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_in_an_older_program(name):
    older = {"vllm:compile_events_total": 22.0,
             "vllm:engine_warmup_seconds": 0.0}
    assert layers.read(name, ctx(older)) is None


def test_miss_share_without_a_compile_request_is_left_out():
    nothing = dict(OPEN, **{"vllm:compile_cache_hits_total": 0.0,
                            "vllm:compile_cache_misses_total": 0.0})
    assert layers.read("compile_cache_miss_pct", ctx(nothing)) is None
