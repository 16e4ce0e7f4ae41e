"""`decode_attn_slab_path_pct`: the share of the decode dispatches'
attention calls that ran the paged decode kernel's slab body, read by
`prom_ratio` from two counters on recorded `/metrics` text. 100 where the
kernel's predicate holds for the served geometry (OLMoE, Ouro), 0.0 where
it does not (Qwen3: the cell that bypasses the mechanism), never `None`
for a program that exports both series: a listed metric that prints
nothing is a refused run."""

import json
import os
import types

from chipbench import layers, prom

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "decode_attn_slab_path_pct"

# what an engine's /metrics carries of the two series, at start-up and
# after 90 decode dispatches of 8 cache layers (one fused iteration each)
HEAD = """\
# HELP vllm:decode_dispatches_total decode_multi dispatches issued (decode-only steps)
# TYPE vllm:decode_dispatches_total counter
vllm:decode_dispatches_total{model_name="m"} %(dispatches)s
# HELP vllm:decode_attn_calls_total Attention calls of the decode dispatches (fused iterations x cache layers a dispatch)
# TYPE vllm:decode_attn_calls_total counter
vllm:decode_attn_calls_total{model_name="m"} %(calls)s
# HELP vllm:decode_attn_slab_calls_total Those that ran the Pallas decode kernel's slab body (one query row a KV head, bf16 cache, 128-wide heads)
# TYPE vllm:decode_attn_slab_calls_total counter
vllm:decode_attn_slab_calls_total{model_name="m"} %(slab)s
"""


def _ctx(open_text, close_text):
    return types.SimpleNamespace(
        prom_open=prom.parse(open_text), prom_close=prom.parse(close_text),
        manifest={})


def _scrape(dispatches, calls, slab):
    return HEAD % {"dispatches": float(dispatches), "calls": float(calls),
                   "slab": float(slab)}


def test_reads_100_where_every_call_took_the_slab_body():
    c = _ctx(_scrape(10, 80, 80), _scrape(100, 800, 800))
    assert layers.read(NAME, c) == 100.0


def test_reads_zero_not_none_where_the_numerator_stands_still():
    # both series exported from start-up at 0; the slab count never moves
    c = _ctx(_scrape(0, 0, 0), _scrape(90, 720, 0))
    value = layers.read(NAME, c)
    assert value == 0.0 and value is not None


def test_reads_nothing_from_a_program_without_the_counters():
    old = "vllm:decode_dispatches_total 5.0\n"
    assert layers.read(NAME, _ctx(old, old)) is None


def test_file_matches_its_benchmark_entry():
    spec = layers.load_spec(NAME)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    (entry,) = [m for m in bm["per_layer"] if m["name"] == NAME]
    assert bm["per_layer"][-1] is entry  # appended, nothing moved
    assert (spec["layer"], spec["unit"], spec["source"], spec["reader"]) == (
        entry["layer"], entry["unit"], entry["source"], "prom_ratio")
    assert entry["moves"] == "tpot_p50_ms" and entry["workloads"] == [
        "qwen3-8b-l16.decode-heavy", "olmoe-1b-7b-l8.decode-heavy",
        "ouro-2.6b.decode-pool-bound"]
    assert not os.path.exists(os.path.join(layers.DIR, NAME + ".py"))
